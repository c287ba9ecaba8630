"""Host seconds per fit between the levels' device work, from the program's
own span tree: ``interlevel/level<l>/gather`` (the partition expanded and
X, S, P, C and alpha gathered into cluster batches) and
``interlevel/level<l>/select`` (alpha copied to the host and the support
vectors selected), level 0's selection included."""
import re

NAME = re.compile(r"^interlevel/level\d+/(gather|select)$")


def read(inputs):
    sp = inputs.counters.get("spans") or {}
    hit = [v for k, v in sp.items() if NAME.match(k)]
    return sum(hit) if hit else None
