"""New tokens the clients received per second of the window; below the knee it follows the offered rate and decides nothing."""


def read(ctx):
    return ctx["counters"].get("out_tokens_per_s")
