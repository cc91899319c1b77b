"""sim_self_share.<kind>: the share of the traced window spent in the
simulator's own host code: the summed self time of the program's
``sim.*`` spans (a span's duration less the part its child spans cover)
over the window (``repro.obs``). None without the program's record."""
from trace_reduce import _union


def read(name, m):
    rec = m.get("program")
    if not rec or not m["window_s"]:
        return None
    spans = rec["spans"]
    children: dict = {}
    for s, d, _, parent, _ in spans:
        if parent >= 0:
            children.setdefault(parent, []).append([s, s + d])
    self_ns = 0
    for i, (s, d, n, _, _) in enumerate(spans):
        if n.startswith("sim."):
            covered = _union(children.get(i, []))
            self_ns += d - sum(e - b for b, e in covered)
    return 100.0 * self_ns * 1e-9 / m["window_s"]
