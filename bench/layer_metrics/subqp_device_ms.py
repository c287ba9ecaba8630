"""Device milliseconds per call of the ``small_qp_sweep`` Pallas kernel
(``kernels/small_qp.py``): the conquer's B×B sub-QP sweep, one call per
level-0 block iteration.  The summed device time of its events in the
traced window over their number.

The kernel's events are the ``tpu_custom_call`` ops the trace names
``%small_qp_sweep.<k>``.  A program whose sweep runs as an XLA loop has no
such events, and the metric is not reported.
"""
import re

OP = re.compile(r"^%small_qp_sweep(\.\d+)? = .*custom-call\(")


def read(inputs):
    t = inputs.trace
    if t is None:
        return None
    ev = t.ops(lambda name: bool(OP.match(name)))
    if not ev:
        return None
    return sum(e.dur for e in ev) * 1e-6 / len(ev)
