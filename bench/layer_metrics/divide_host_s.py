"""Host seconds per fit in the divide step's host work after the distances
reach the host: the balanced assignment (``divide/level<l>/balance``,
``kkmeans.balanced_assign``) and the partition's layout
(``divide/level<l>/partition``, ``Partition.build``), from the program's own
span tree.  Both lie inside ``divide/level<l>/cluster`` (``divide_s``)."""
import re

NAME = re.compile(r"^divide/level\d+/(balance|partition)$")


def read(inputs):
    sp = inputs.counters.get("spans") or {}
    hit = [v for k, v in sp.items() if NAME.match(k)]
    return sum(hit) if hit else None
