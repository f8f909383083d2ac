"""Roofline share of the decode step's dense MLPs in a configuration of double layers: their weights (two MLPs of three `hidden_size x ffn_hidden_size` matrices a double layer), read once a step, over the time the operations under `scmoe.dense` took."""

from benchmarks import longcat_cost


def read(ctx):
    return longcat_cost.dense_roofline(ctx)
