"""Mean share of the engine's decode slots that decode in each batched
decode step of the window (host counters: tokens seen per request)."""


def read(run):
    occ = [len(s.decode_lens) / run.max_slots for s in run.steps
           if s.decode_lens]
    return 100.0 * sum(occ) / len(occ) if occ else None
