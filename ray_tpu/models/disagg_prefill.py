"""Disaggregated prefill: a prefill replica computes prompt KV, a
decode replica consumes it — KV crosses processes through a typed
tensor channel, never the pickle path.

Reference: python/ray/llm/_internal/serve/engines/vllm/kv_transfer/ —
the reference splits prefill and decode across engine replicas and
ships KV blocks through a connector (NIXL / shared memory). The TPU
rebuild: the prefill replica runs ONE bucketed prefill program per
prompt-length bucket, writes the resulting [L, max_len, kvH, D] row
into a fixed-shape ``TensorChannel`` (shared-memory, zero pickle), and
the decode replica installs it straight into its paged pool
(``PagedBatcher.submit_prefilled``) and continuous-batches decode.

Why it matters on TPU: prefill is compute-bound (MXU saturating) while
decode is memory-bound (HBM streaming); separate replicas mean each
can be provisioned and batched on its own terms — the reference's
motivation, unchanged by the hardware.

Pairing protocol: one caller submits ``prefill.remote`` then
``decode.remote`` for each request; actor task ordering per caller
plus the channel's one-slot ack backpressure keep the KV rows and
decode admissions in lockstep — no sequence numbers needed. The
channel is same-host shared memory; cross-host disaggregation rides
the object-store path instead (``RowHandle`` falls back to plasma).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

import ray_tpu
from ray_tpu.experimental.channel import TensorChannel
from ray_tpu.models.decoding import SamplingParams
from ray_tpu.models.families import only_kv_rows
from ray_tpu.models.transformer import TransformerConfig

_TRANSPORT_DTYPE = "float32"  # numpy has no bfloat16; rows are cast


def _row_shape(cfg: TransformerConfig, max_len: int):
    # [2 (k/v), L, max_len, kvH, D]: the K/V layers a sequence keeps
    # (`cfg.kept`: a looped model's every pass has its own)
    return (2, cfg.full_layers, max_len, cfg.kv_heads, cfg.hd)


@ray_tpu.remote(max_concurrency=1)
class PrefillReplica:
    """Computes prompt KV rows and streams them into the channel.

    max_concurrency=1: a single-threaded actor executes its tasks in
    enqueue order, so KV rows enter the channel in the same order the
    engine assigned ticket numbers — the decode side's ticket gate
    (DecodeReplica.generate) then pairs rows to requests exactly."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int,
                 channel: TensorChannel):
        from ray_tpu.models.continuous_batching import PrefillPrograms

        self.cfg = cfg
        self.max_len = max_len
        self.channel = channel
        # the scheduler's own prefill, one program per prompt-length bucket
        self.programs = PrefillPrograms(cfg, params, max_len)

    def prefill(self, tokens: Sequence[int]):
        """Returns (n_tokens, last_logits) on the object path; the KV
        row goes out-of-band through the tensor channel."""
        last, row_k, row_v, *_ = self.programs._prefill(tokens)
        row = np.zeros(_row_shape(self.cfg, self.max_len),
                       _TRANSPORT_DTYPE)
        row[0, :, :row_k.shape[1]] = np.asarray(row_k, np.float32)
        row[1, :, :row_v.shape[1]] = np.asarray(row_v, np.float32)
        self.channel.write(row, timeout=120.0)
        return len(tokens), np.asarray(last, np.float32)


@ray_tpu.remote(max_concurrency=4)
class DecodeReplica:
    """Owns the paged pool; admits prefilled rows and decodes."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int,
                 slots: int, page_size: int, reader):
        import threading

        from ray_tpu.models.paged_kv import PagedBatcher

        # the batcher's tree is the replica's one: it re-lays the leaves
        # its decode step reads in another layout and deletes those given
        self.batcher = PagedBatcher(cfg, params, max_len=max_len,
                                    slots=slots, page_size=page_size)
        self.reader = reader
        # ticket gate: generate() may run on several actor threads, but
        # channel reads MUST happen in the engine's ticket order or two
        # same-length prompts could swap KV rows undetectably
        self._next_ticket = 0
        self._ticket_cv = threading.Condition()

    def generate(self, tokens: Sequence[int], prefill_meta,
                 sampling: Optional[SamplingParams] = None,
                 ticket: int = 0) -> List[int]:
        """prefill_meta is PrefillReplica.prefill's return (resolved by
        the runtime when the prefill task finishes — by which time its
        KV row is already in, or entering, the channel)."""
        n, last_logits = prefill_meta
        assert n == len(tokens), "prefill/decode pairing broke"
        with self._ticket_cv:
            while ticket != self._next_ticket:
                if not self._ticket_cv.wait(timeout=300.0):
                    raise TimeoutError(
                        f"ticket {ticket} starved (next="
                        f"{self._next_ticket})")
            row = self.reader.read(timeout=120.0)
            self._next_ticket += 1
            self._ticket_cv.notify_all()
        fut = self.batcher.submit_prefilled(
            tokens, row[0], row[1], last_logits, sampling)
        return fut.result(timeout=300.0)

    def stats(self) -> Dict[str, int]:
        return dict(self.batcher.stats)

    def close(self) -> bool:
        self.batcher.shutdown()
        return True


class DisaggPrefillEngine:
    """Two-replica engine: ``generate`` fans a request through the
    prefill replica into the decode replica and returns the sampled
    tokens. Construction is driver-side; both replicas live on the
    local node (the KV channel is shared memory)."""

    def __init__(self, cfg: TransformerConfig, params, max_len: int = 256,
                 slots: int = 4, page_size: int = 32,
                 num_cpus: float = 0.5):
        # the channel's row is K and V alone; decoding from it would start
        # every sequence from a new sequence's state, ring or rows
        only_kv_rows(cfg, "the KV channel does not carry them: serve it from "
                     "one replica (ContinuousBatcher)")
        self.channel = TensorChannel(_row_shape(cfg, max_len),
                                     _TRANSPORT_DTYPE)
        self.prefiller = PrefillReplica.options(num_cpus=num_cpus).remote(
            cfg, params, max_len, self.channel)
        self.decoder = DecodeReplica.options(num_cpus=num_cpus).remote(
            cfg, params, max_len, slots, page_size, self.channel.reader(0))
        self._ticket = 0

    def generate(self, tokens: Sequence[int],
                 sampling: Optional[SamplingParams] = None):
        """Returns a ref resolving to the sampled token list."""
        ticket = self._ticket
        self._ticket += 1
        meta = self.prefiller.prefill.remote(list(tokens))
        return self.decoder.generate.remote(list(tokens), meta, sampling,
                                            ticket=ticket)

    def stats(self) -> Dict[str, int]:
        return ray_tpu.get(self.decoder.stats.remote())

    def shutdown(self) -> None:
        try:
            ray_tpu.get(self.decoder.close.remote(), timeout=30)
        except Exception:  # noqa: BLE001
            pass
        for a in (self.prefiller, self.decoder):
            try:
                ray_tpu.kill(a)
            except Exception:  # noqa: BLE001
                pass
        try:
            self.channel.close()
        except Exception:  # noqa: BLE001
            pass
