"""staged_mb_per_update.<kind>: host bytes built for transfer per update
of the window (a KD epoch, a server round or receive):
``staged_bytes`` (``repro.obs`` counter) / 10^6 / updates. None without
the program's record."""


def read(name, m):
    counts = (m.get("program") or {}).get("counts", {})
    if "staged_bytes" not in counts or not m.get("updates"):
        return None
    return counts["staged_bytes"] * 1e-6 / m["updates"]
