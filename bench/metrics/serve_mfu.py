"""The whole serving step's share of the chip's peak: over every prefill
chunk and decode step of the window, the least time at the v5e peaks
for its operations and bytes (``bench.flops``), summed, over the
window's wall time.  It bounds any claim on ``output_tokens_per_s``."""

from bench import flops
from bench.peaks import roofline_s

BYTES = {"bf16": 2, "bfloat16": 2, "f32": 4, "float32": 4, "int8": 1}


def read(run):
    eng = run.cell.traffic["engine"]
    wb, kb = BYTES[eng["weights_dtype"]], BYTES[eng["kv_dtype"]]
    chunk = int(eng["prefill_chunk"])
    need = 0.0
    for s in run.steps:
        if s.decode_lens:
            need += roofline_s(*flops.decode_step(run.dims, s.decode_lens,
                                                  wb, kb), run.peaks)
        for p in s.prefills:
            for o, n in flops.prefill_chunks(p, chunk):
                need += roofline_s(*flops.prefill_chunk(run.dims, o, n,
                                                        wb, kb), run.peaks)
    t0, t1 = run.window
    return 100.0 * need / (t1 - t0) if need else None
