"""Of the window's routed assignments of real rows, the share that went to experts held on this chip (`moe_assignments_held / moe_assignments`): near the share of the experts that is held."""


def read(ctx):
    moe = ctx["counters"].get("moe") or {}
    if not moe.get("moe_assignments") or "moe_assignments_held" not in moe:
        return None
    return moe["moe_assignments_held"] / moe["moe_assignments"]
