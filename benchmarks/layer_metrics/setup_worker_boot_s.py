"""`ray_tpu.setup.worker.boot` of the process that holds the chip: its spawn -> its actor's arrival; 0 where it was `pooled` (the boot predates the lease)."""

from benchmarks import setup_record as S


def read(ctx):
    rec = S.record()
    worker = S.chip_worker(rec)
    boots = [e for e in rec if e["name"] == S.P + "worker.boot"
             and e["worker"] == worker]
    if not boots:
        return None
    return 0.0 if boots[0]["attrs"].get("pooled") else boots[0]["dur"]
