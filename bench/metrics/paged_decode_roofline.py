"""Share of its roofline the paged decode kernel reaches: the least time
the chip needs for the kernel's operations and bytes in the window
(``bench.flops.paged_decode_call``, one call per layer per decode step)
over the kernel's device time in the trace."""

from bench import flops
from bench.peaks import roofline_s

#: the kernel is the one Pallas call inside the jitted decode program
PROGRAM = "serve_step"
KV_BYTES = {"bf16": 2, "f32": 4, "int8": 1}


def read(run):
    t = run.trace.kernel_s(PROGRAM)
    kv = KV_BYTES[run.cell.traffic["engine"]["kv_dtype"]]
    need = sum(run.dims.layers * roofline_s(
        *flops.paged_decode_call(run.dims, s.decode_lens, kv), run.peaks)
        for s in run.steps if s.decode_lens)
    return 100.0 * need / t if t and need else None
