"""Data plane: distributed shuffles (no driver materialization), file IO
round-trips, and the streaming read->transform->shuffle->iterate pipeline
(reference: _internal/planner/{sort,random_shuffle}.py two-stage shuffle,
read_api.py:1128 parquet, streaming_executor.py:100)."""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rdata


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_distributed_sort(cluster):
    ds = rdata.range(10_000, override_num_blocks=8).random_shuffle(seed=7)
    out = ds.sort("id").materialize()
    ids = np.concatenate([b["id"] for b in out.iter_blocks()])
    assert (ids == np.arange(10_000)).all()


def test_distributed_sort_descending(cluster):
    ds = rdata.range(5_000, override_num_blocks=4)
    ids = np.concatenate([b["id"] for b in ds.sort("id", descending=True).iter_blocks()])
    assert (ids == np.arange(4_999, -1, -1)).all()


def test_distributed_shuffle_is_permutation(cluster):
    ds = rdata.range(8_000, override_num_blocks=4).random_shuffle(seed=3)
    ids = np.concatenate([b["id"] for b in ds.iter_blocks()])
    assert len(ids) == 8_000
    assert not (ids == np.arange(8_000)).all()  # actually shuffled
    assert (np.sort(ids) == np.arange(8_000)).all()  # a permutation


def test_distributed_groupby(cluster):
    ds = rdata.range(1_000, override_num_blocks=5).add_column(
        "bucket", lambda b: b["id"] % 10
    )
    out = ds.groupby("bucket").count().materialize()
    rows = sorted(out.take_all(), key=lambda r: r["bucket"])
    assert len(rows) == 10
    assert all(r["count()"] == 100 for r in rows)


def test_repartition_distributed(cluster):
    ds = rdata.range(1_024, override_num_blocks=2).repartition(8)
    blocks = list(ds.iter_blocks())
    assert len(blocks) == 8
    assert sum(len(b["id"]) for b in blocks) == 1_024


def test_parquet_roundtrip_pipeline(cluster, tmp_path):
    src = rdata.range(2_000, override_num_blocks=4).add_column(
        "x", lambda b: b["id"].astype(np.float64) * 0.5
    )
    paths = src.write_parquet(str(tmp_path / "pq"))
    assert len(paths) == 4 and all(os.path.exists(p) for p in paths)

    # the VERDICT's acceptance pipeline: read_parquet -> map_batches ->
    # shuffle -> iter_batches, streaming through refs only
    ds = (
        rdata.read_parquet(str(tmp_path / "pq"))
        .map_batches(lambda b: {"id": b["id"], "y": b["x"] * 2.0})
        .random_shuffle(seed=11)
    )
    seen = 0
    ssum = 0.0
    for batch in ds.iter_batches(batch_size=256):
        seen += len(batch["id"])
        ssum += float(batch["y"].sum())
    assert seen == 2_000
    assert ssum == float(np.arange(2_000).sum())  # y = id


def test_csv_json_roundtrip(cluster, tmp_path):
    src = rdata.from_items([{"a": i, "b": f"s{i}"} for i in range(100)])
    src.write_csv(str(tmp_path / "csv"))
    back = rdata.read_csv(str(tmp_path / "csv"))
    rows = sorted(back.take_all(), key=lambda r: r["a"])
    assert len(rows) == 100 and rows[5]["b"] == "s5"

    src.write_json(str(tmp_path / "json"))
    back = rdata.read_json(str(tmp_path / "json"))
    assert back.count() == 100


def test_iter_jax_batches_from_pipeline(cluster):
    ds = rdata.range(512, override_num_blocks=2).map_batches(
        lambda b: {"x": b["id"].astype(np.float32)}
    )
    batches = list(ds.iter_jax_batches(batch_size=128))
    assert len(batches) == 4
    assert float(sum(b["x"].sum() for b in batches)) == float(np.arange(512).sum())


# ---------------------------------------------------------------------------
# streaming executor (reference: streaming_executor.py:100,
# backpressure_policy/, map_operator.py:196 actor pools)
# ---------------------------------------------------------------------------
def test_streaming_stage_overlap(cluster, tmp_path):
    """VERDICT acceptance: stage 2 starts processing early blocks while
    stage 1 is still processing later blocks (no barrier between map
    stages of a read -> map_batches -> ingest pipeline).

    Shown by an event, not by racing sleeps against the actor pool's
    start-up: stage 1 does not finish its LAST block before stage 2 has
    been handed its first. A streaming executor gets there however slow
    the actors start; one with a barrier between the stages never does,
    and the last block gives up at its timeout and says so."""
    blocks, rows = 24, 64
    src = rdata.range(blocks * rows, override_num_blocks=blocks).materialize()
    src.write_parquet(str(tmp_path / "pq"))
    marker = str(tmp_path / "stage2_began")

    def stage1(b):
        saw_stage2 = True
        if b["id"].max() == blocks * rows - 1:  # the last block
            deadline = time.time() + 90.0
            while not os.path.exists(marker) and time.time() < deadline:
                time.sleep(0.02)
            saw_stage2 = os.path.exists(marker)
        out = dict(b)
        out["saw_stage2"] = np.full(len(b["id"]), saw_stage2)
        out["t1_end"] = np.full(len(b["id"]), time.time())
        return out

    class Stage2:
        """Stateful: exercised via the actor-pool map operator."""

        def __init__(self):
            self.blocks = 0

        def __call__(self, b):
            started = time.time()
            open(marker, "a").close()
            self.blocks += 1
            out = dict(b)
            out["t2_start"] = np.full(len(b["id"]), started)
            return out

    ds = (rdata.read_parquet(str(tmp_path / "pq"))
          .map_batches(stage1)
          .map_batches(Stage2, concurrency=2))
    t1_end, t2_start, saw_stage2 = [], [], []
    for batch in ds.iter_batches(batch_size=None):
        t1_end.append(batch["t1_end"].max())
        t2_start.append(batch["t2_start"].min())
        saw_stage2.append(bool(batch["saw_stage2"].all()))
    assert len(t1_end) == blocks
    assert all(saw_stage2), (
        "stages ran serially: stage 1's last block waited 90 s and stage 2 "
        "had not been given a block")
    # overlap: some stage-2 work began BEFORE the last stage-1 block done
    assert min(t2_start) < max(t1_end), (
        f"stages ran serially: first t2 {min(t2_start):.3f} >= "
        f"last t1 {max(t1_end):.3f}")
