"""useful_step_ratio.<kind>: the share of the client clip-steps the fed
engines ran that were not padding: 100·``clip_steps_useful`` /
``clip_steps_executed`` (``repro.obs`` counters). None without them."""


def read(name, m):
    counts = (m.get("program") or {}).get("counts", {})
    if not counts.get("clip_steps_executed"):
        return None
    return (100.0 * counts["clip_steps_useful"]
            / counts["clip_steps_executed"])
