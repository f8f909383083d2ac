"""JaxTrainer.fit() called -> the first train step done (host clock in the loop): bring-up, init_state, the step's compile or cache read."""


def read(ctx):
    return ctx["counters"].get("fit_to_first_step_s")
