"""Host clock around serve.run + start_http_proxy: worker start, parameter init, engine construction."""


def read(ctx):
    return ctx["counters"].get("replica_start_s")
