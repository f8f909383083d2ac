"""Expert assignments per real token and layer over the window: `num_experts_per_tok` exactly, anything less is a dropped assignment (anything more a counted pad row)."""


def read(ctx):
    moe = ctx["counters"].get("moe")
    if not moe or not moe["moe_rows"]:
        return None
    return moe["moe_assignments"] / (moe["moe_rows"] * moe["layers"])
