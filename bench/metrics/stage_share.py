"""stage_share.<kind>: the share of the traced window in which the host
built arrays for transfer: 100·|union of the program's ``data.stack``
and ``fed.pad`` spans| / window (``repro.obs``). None without the
program's record."""
from trace_reduce import _union

STAGE = ("data.stack", "fed.pad")


def read(name, m):
    rec = m.get("program")
    if not rec or not m["window_s"]:
        return None
    busy = _union([s, s + d] for s, d, n, _, _ in rec["spans"]
                  if n in STAGE)
    return 100.0 * sum(e - s for s, e in busy) * 1e-9 / m["window_s"]
