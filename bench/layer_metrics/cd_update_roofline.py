"""Share of its roofline that the ``cd_column_update`` Pallas kernel
(``kernels/cd_update.py``) reaches in the traced window: the least time of
its calls (``bench/work/cd_update.py`` and the chip's peaks) over the summed
device time of its events.

The kernel's events are the ``tpu_custom_call`` ops the trace names
``%cd_column_update.<k>``; each call's shapes are read from the op's own
HLO text: X ``f32[n,d]`` (first operand) and the block ``f32[B,d]`` (third).
An event whose shapes cannot be read fails the run.
"""
import re

OP = re.compile(r"^%cd_column_update(\.\d+)? = .*custom-call\(")
SHAPE = re.compile(r"f32\[(\d+),(\d+)\]")


def read(inputs):
    t = inputs.trace
    if t is None or inputs.peaks is None:
        return None
    ev = t.ops(lambda name: bool(OP.match(name)))
    if not ev:
        return None
    least = spent = 0.0
    for e in ev:
        operands = e.name.split("custom-call(", 1)[1]
        shapes = SHAPE.findall(operands)
        if len(shapes) < 3:
            raise ValueError(f"cannot read the shapes of {e.name[:200]}")
        (n, d), (B, _) = shapes[0], shapes[2]
        flops, nbytes = inputs.work("cd_update")(
            {"n": int(n), "d": int(d), "B": int(B)})
        least += max(flops / inputs.peaks["flops_per_s"],
                     nbytes / inputs.peaks["hbm_bytes_per_s"])
        spent += e.dur * 1e-9
    return 100.0 * least / spent
