"""Model FLOP utilization of training: the model's operations per token
(``bench.flops.train_flops_per_token``: forward and backward, no
recomputation) times the tokens trained per second in the window, over
the chip's bf16 peak."""

from bench import flops


def read(run):
    t0, t1 = run.window
    per_tok = flops.train_flops_per_token(run.dims,
                                          int(run.cell.traffic["seq_len"]))
    rate = run.steps_run * run.tokens_per_step / (t1 - t0)
    return 100.0 * per_tok * rate / run.peaks["bf16_flops"]
