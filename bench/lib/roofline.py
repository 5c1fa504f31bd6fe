"""A kernel's share of its roofline over a traced window."""
from __future__ import annotations

from lib import kernels as K
from lib import trace as TR


def share(record: dict, match, work) -> float | None:
    """Summed least time over summed device time of the traced calls that
    ``match(call)`` picks in the programs the window drove, each call's
    work counted by ``work(call)`` (``{"flops", "bytes"}``), in %; None
    where the window ran no such call."""
    tr = record.get("trace")
    if tr is None:
        return None
    least = spent = 0.0
    for module, calls in record.get("programs", {}).items():
        mine = {n: work(c) for n, c in calls.items() if match(c)}
        for e in TR.kernel_events(tr, mine, module):
            least += K.least_seconds(mine[e.name], record["peak"])
            spent += e.dur
    if spent <= 0:
        return None
    return 100.0 * least / spent
