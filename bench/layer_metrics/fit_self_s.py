"""Host seconds per fit that no phase span explains: the root span ``fit``
(``dcsvm.fit``) minus its phase spans ``divide/level<l>/{cluster,solve}``,
``interlevel/level<l>/{gather,select}`` and ``conquer/{refine,solve}``,
from the program's own span tree."""
import re

PHASE = re.compile(r"^(divide/level\d+/(cluster|solve)"
                   r"|interlevel/level\d+/(gather|select)"
                   r"|conquer/(refine|solve))$")


def read(inputs):
    sp = inputs.counters.get("spans") or {}
    if "fit" not in sp:
        return None
    return sp["fit"] - sum(v for k, v in sp.items() if PHASE.match(k))
