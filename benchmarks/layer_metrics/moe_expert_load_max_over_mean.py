"""The window's fullest expert over the mean expert, from the engine's per-expert load (all layers summed)."""


def read(ctx):
    load = (ctx["counters"].get("moe") or {}).get("expert_load")
    if not load or not sum(load):
        return None
    return max(load) * len(load) / sum(load)
