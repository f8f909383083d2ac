"""Event-type registry: the closed set of bus event schemas.

Every ``record_event(etype, ...)`` call site must use a type declared
here (enforced by raycheck RC009) — an undeclared literal is a typo or
an undocumented schema, and a name built from an f-string is an
unbounded-cardinality bug waiting for the aggregator's memory. The
registry is a plain dict literal on purpose: RC009 reads it via AST,
no imports required.

The value strings document the payload contract a consumer (obsdump,
the aggregator, the state API) can rely on; they are not validated at
record time — recording stays two deque appends.
"""

from __future__ import annotations

EVENT_TYPES = {
    # tracing (observability/tracing.py — the one span producer)
    "span": "trace_id, span_id, parent_span_id, name, kind, job_id, "
            "ts, dur, status, attrs",
    # core-worker task path (gated on tracing.active())
    "task_state": "task_id, state, job_id, ...",
    "object_put": "size, job_id, inline",
    "object_get": "size, job_id, inline",
    # GCS control plane
    "actor_restart": "actor_id, restarts_left / exhausted",
    "NODE_DRAIN_START": "node_id, reason, deadline_s",
    "NODE_DRAIN_COMPLETE": "node_id, reason, duration_s, forced",
    # collectives (util/collective + observability/collective.py)
    "collective_op": "op, nbytes, world_size, rank, algo, codec, "
                     "topology, dur_s, mb_per_s, phases",
    "collective_epoch": "group, epoch, rank, members",
    "collective_failure": "group, epoch, rank, op, phase, then either "
                          "dead_ranks (confirmed death) or "
                          "suspect_ranks + confirmed=False (deadline "
                          "exhausted before the probe confirmed)",
    # control-plane lifecycle timelines (observability/timeline.py)
    "actor_lifecycle": "actor_id, phase, mono, job_id, node_id?",
    "task_lifecycle": "task_id, phase, mono, job_id",
    # bring-up intervals (observability/timeline.py ``setup_phase``, the
    # one producer): ``mono`` / ``ts`` are the interval's START on
    # time.monotonic() / time.time(), ``name`` is a key of SETUP_PHASES
    "setup_phase": "name, mono, dur, attrs",
    # flight-recorder dumps (observability/dump.py)
    "debug_dump": "reason, path, source",
    # podracer stage accounting (rllib/podracer/obs.py snapshots)
    "podracer_stage": "stages {name: {s, n}}, role",
}

# Set-up phases (observability/timeline.py ``setup_phase``): what a process
# does ONCE before its first useful step, booked as one ``setup_phase`` event
# an interval, always on (two clock reads and one ``record_event`` a phase,
# nothing a step, a token or a request). A closed vocabulary, a dict literal
# with literal keys like EVENT_TYPES (RC009 reads it via AST); a name that is
# not here raises. Children lie inside their parent's interval. Value: the
# process that books it, what the interval covers, [its attrs].
SETUP_PHASES = {
    # driver: _private/worker.py::init, _private/node.py::Node.start
    "ray_tpu.setup.init": "driver: ray_tpu.init(), whole",
    "ray_tpu.setup.init.gcs": "driver: the GCS spawned -> it answers Ping",
    "ray_tpu.setup.init.raylet":
        "driver: the raylet spawned -> its port bound: the object store's "
        "daemon started; it registers with the GCS after [native_built: "
        "the daemon was built first, a fresh checkout's first run]",
    "ray_tpu.setup.init.connect":
        "driver: the core worker's connections, the job's registration",
    # driver: serve/controller.py::run, serve/http_proxy.py
    "ray_tpu.setup.serve.run": "driver: serve.run(), whole",
    "ray_tpu.setup.serve.controller":
        "driver: the Serve controller looked up or created",
    "ray_tpu.setup.serve.deploy":
        "driver: ctl.deploy sent -> its snapshot back: scheduling, the "
        "lease, the worker's start, the replica's __init__, the health "
        "check",
    "ray_tpu.setup.serve.proxy": "driver: the HTTP proxy bound and serving",
    # every worker: _private/workers/default_worker.py::CreateActor
    "ray_tpu.setup.worker.boot":
        "worker, once a process: its spawn (RAY_TPU_WORKER_SPAWNED_MONO) -> "
        "its first actor's arrival [ready_s: spawn -> registered with the "
        "raylet; pooled: it then idled in the pool for longer than that, so "
        "the interval is no part of the actor's bring-up]",
    "ray_tpu.setup.actor.init":
        "worker: an actor's runtime env applied, its class and arguments "
        "unpickled, the class's __init__ [actor_id, cls]",
    # the replica: llm/engine.py, llm/serving.py, models/continuous_batching
    "ray_tpu.setup.engine.build":
        "replica: the engine's constructor, whole (LLMServer.__init__)",
    "ray_tpu.setup.engine.backend":
        "replica: the first jax.devices(): the backend's start, unless "
        "the caller's code touched it before",
    "ray_tpu.setup.engine.params":
        "replica: init_params (a pattern's _draw) or the checkpoint's "
        "restore, waited for [bytes, programs: executables obtained]",
    "ray_tpu.setup.engine.cache":
        "replica: the slots' cache allocated, waited for [bytes]",
    # parallel/bootstrap.py::program_phase: FirstCall, round every jitted
    # program of the engine and round the train step, and the engine's
    # compile of its decode step (ContinuousBatcher.__init__)
    "ray_tpu.setup.program":
        "chip process: a jitted program's FIRST call, waited for [program: "
        "prefill_<bucket> | decode | install | reset_state | sample_first | "
        "train_step; trace_s, lower_s, compile_s: JAX's own events over the "
        "call (compile_s holds a cache read); cache: hit | miss | none; "
        "first_run_s: the call's wall less those three: the executable's "
        "load, the first transfers, the first execution]. `decode` is the "
        "engine's BUILD and not a call: the step compiled ahead of the "
        "cache's allocation with the weights' layouts its own to choose, "
        "first_run_s the re-lay of the leaves that differ [weights_relaid: "
        "their paths; weights_relaid_bytes]",
    # train/step.py::make_train_step
    "ray_tpu.setup.step.build":
        "chip process: make_train_step's own Python (shapes, shardings, the "
        "trainable mask)",
    "ray_tpu.setup.step.settle":
        "chip process: the first call's way down REMAT_LADDER, whole "
        "[rungs_tried: rungs the step stood on, 1 where no limit can be "
        "read; kept: the names taken, as run.remat_kept]",
    "ray_tpu.setup.step.rung":
        "chip process: one rung compiled ahead of time [kept; lower_s: its "
        "tracing and lowering; compile_s; bytes: what it needs, None where "
        "the compiler refused it; fits]",
}

# Device spans (observability/tracing.py ``device_span``): host spans on
# the profiler's clock, ``ray_tpu.<layer>.<site>``. Written once here so
# that sites and trace readers cannot drift. The value strings name the
# stats an event carries (fixed when the span opens): only what a reader
# or an operator's question needs, since a stat is built on every pass
# whether or not a profile is running.
ENGINE_IDLE = "ray_tpu.engine.idle"
ENGINE_STEP = "ray_tpu.engine.step"
ENGINE_ADMIT = "ray_tpu.engine.admit"
ENGINE_PREFILL_DISPATCH = "ray_tpu.engine.prefill_dispatch"
ENGINE_INSTALL_DISPATCH = "ray_tpu.engine.install_dispatch"
ENGINE_FIRST_TOKEN_SYNC = "ray_tpu.engine.first_token_sync"
ENGINE_DECODE_DISPATCH = "ray_tpu.engine.decode_dispatch"
ENGINE_SAMPLE_SYNC = "ray_tpu.engine.sample_sync"
ENGINE_EMIT = "ray_tpu.engine.emit"
REPLICA_DETOKENIZE = "ray_tpu.replica.detokenize"
WORKER_STREAM_YIELD = "ray_tpu.worker.stream_yield"
WORKER_STREAM_RPC = "ray_tpu.worker.stream_rpc"

DEVICE_SPANS = {
    # pump thread of models/continuous_batching.py
    ENGINE_IDLE: "",
    # the pump's three clocks (`ContinuousBatcher.stats`) as last booked
    # (every few passes: `_book`): two bookings in a trace give the
    # counters of the time between them
    ENGINE_STEP: "step, pump_step_s, pump_sync_s, pump_cpu_s",
    ENGINE_ADMIT: "bucket, prompt_len, queued_ms",
    ENGINE_PREFILL_DISPATCH: "",
    ENGINE_INSTALL_DISPATCH: "",
    ENGINE_FIRST_TOKEN_SYNC: "",
    # `rows`: the positions the step's sequences hold. A layer pattern's
    # engine (models/laguna.py) adds `window_rows`: of `rows`, those a
    # window layer's ring holds (min(rows, window) a slot). What a step's
    # attention reads for them and whether its sampling drew or sorted are
    # counters (`kv_rows_read`, `steps_sampled`, `steps_sorted`)
    ENGINE_DECODE_DISPATCH: "active, ahead, rows",
    ENGINE_SAMPLE_SYNC: "",
    ENGINE_EMIT: "",
    # handler threads of a replica, one a stream. A streamed token goes
    # pump `engine.emit` -> the stream's queue -> llm/serving.py
    # `text_deltas` (`replica.detokenize`: a short window of the answer's
    # last ids decoded, whatever its length; `ids` = the ids of the answer
    # so far, `decoded` = the ids this turn handed to `decode`, a handful
    # unless an incomplete character is held, `backlog` = ids emitted and
    # not yet taken, 0 while the handler keeps up) ->
    # _private/workers/default_worker.py
    # `_execute_streaming` (`worker.stream_yield`: one generator item
    # serialised and handed to the caller's `streaming.StreamSender`; the
    # yield waits only when its stream is a whole buffer ahead of its
    # consumer). `worker.stream_rpc` is on the SENDER's thread, one span a
    # StreamingYield CALL: the wire, the caller's handler, the ack back,
    # for the `items` (`bytes` in all) that every stream of this process
    # had handed over for that caller since the call before
    REPLICA_DETOKENIZE: "ids, decoded, backlog",
    WORKER_STREAM_YIELD: "",
    WORKER_STREAM_RPC: "items, bytes",
}

# Scopes INSIDE the compiled programs (`jax.named_scope` at the sites in
# models/): an operation's scope is in the compiled program's text
# (`metadata={op_name=".../cca.attend/..."}`), not in the device trace, which
# names an operation by its HLO line alone; `benchmarks/scope_ops.py` goes
# from one to the other. Value: what runs under the scope.
PROGRAM_SCOPES = {
    "cca.project": "models/zaya.py: the latent q, k and v projections",
    "cca.conv": "models/zaya.py: the two convolutions, the q-k mean, the "
                "value shift, the state's read and write",
    "cca.attend": "models/zaya.py: L2 norm, RoPE, the row write, attention "
                  "over the cache (attend_cached inside it), wo",
    "zaya.router": "models/zaya.py: projection, carried sum, MLP, choice",
    "attn.full": "models/laguna.py: a full layer's projections, RoPE, row "
                 "write, attention over its slots (attend_cached inside "
                 "it), the gate, wo",
    "attn.window": "models/laguna.py: the same of a window layer, over its "
                   "ring",
    "kda.project": "models/kimi_linear.py: a linear-attention layer's q, k "
                   "and v projections",
    "kda.conv": "models/kimi_linear.py: the three depthwise convolutions, "
                "their windows' read and write, SiLU, the L2 norms",
    "kda.gate": "models/kimi_linear.py: the low-rank decay gate, the step "
                "beta, the low-rank output gate",
    "kda.state": "models/kimi_linear.py: a decode step's state update: "
                 "every sequence's matrix state decayed, corrected by one "
                 "rank-one term and read out (read once, written once; "
                 "`engine_stats()['kda_path']` says kernel or plain)",
    "kda.prefill_scan": "models/kimi_linear.py: a prefill's recurrence, a "
                        "chunk of positions at a time (on a TPU one kernel "
                        "that keeps a block of heads' states in fast memory "
                        "over the chunks: `kda_path` says which)",
    "kda.out": "models/kimi_linear.py: the head norm, the output gate, wo",
    "mla.project": "models/kimi_linear.py: a latent-attention sublayer's "
                   "query (through its low-rank factors and their norm, "
                   "where it has them) and latent projections, the latent's "
                   "norm and factor, the row write; in a decode step the key "
                   "expansion absorbed into the query",
    "mla.rotate": "models/kimi_linear.py: the rotation by position of the "
                  "query's rope part and of the one shared key part "
                  "(`mla_rotate`), pairs interleaved, before the key is "
                  "cached",
    "mla.attend": "models/kimi_linear.py: attention: a decode step's over "
                  "the held latent rows (absorbed), a prefill's over keys "
                  "and values expanded from its own fresh rows",
    "mla.out": "models/kimi_linear.py: a decode step's value expansion; wo",
    "gqa.project": "models/kimi_linear.py: a grouped-attention (\"gkv\") "
                   "layer's q, k and v projections AND its output gate's "
                   "(`gqa_gate`: the matrix is read here)",
    "gqa.attend": "models/kimi_linear.py: the row write and attention over "
                  "the slots' K/V rows, no rotation (attend_cached inside "
                  "it: a decode step's kernel over the held rows, a "
                  "prefill's flash forward over its fresh ones). Which "
                  "layers are of which kind and how many cache layers of "
                  "K/V rows a sequence keeps: "
                  "`engine_stats()['layers_by_kind']` / `['kv_layers_kept']`",
    "gqa.gate": "models/kimi_linear.py: the gate's sigmoid times the "
                "attention's output, elementwise",
    "gqa.out": "models/kimi_linear.py: wo",
    "ssm.project": "models/nemotron_h.py: a state-space mixer's input "
                   "projection (gate, convolution channels, steps), the "
                   "steps' softplus and the decay's log",
    "ssm.conv": "models/nemotron_h.py: the depthwise convolution with its "
                "bias over x, B and C, its window's read and write, SiLU",
    "ssm.state": "models/nemotron_h.py: a decode step's state update: every "
                 "sequence's states decayed, one rank-one term a head added "
                 "and read out, D x (read once, written once; the bytes are "
                 "a counter, `state_bytes_rewritten`)",
    "ssm.prefill_scan": "models/nemotron_h.py: a prefill's recurrence, a "
                        "chunk of positions at a time",
    "ssm.norm": "models/nemotron_h.py: the gate, then the norm a group",
    "ssm.out": "models/nemotron_h.py: the output projection",
    "ssm1.project": "models/nemotron_h.py: a Mamba-1 mixer's projections: "
                    "W_in (u and the gate), W_x (the low-rank steps, B and "
                    "C) with the three small norms, W_dt with its bias and "
                    "the softplus",
    "ssm1.conv": "models/nemotron_h.py: the depthwise convolution with its "
                 "bias over u alone, its window's read and write, SiLU",
    "ssm1.state": "models/nemotron_h.py: a decode step's state update for a "
                  "decay by channel and state index: every sequence's states "
                  "decayed (the decay formed inside the kernel), one "
                  "rank-one term added and read out, D x (read once, "
                  "written once; `state_bytes_rewritten` counts the bytes; "
                  "`engine_stats()['ssm_path']` says kernel or plain)",
    "ssm1.prefill_scan": "models/nemotron_h.py: a prefill's selective scan "
                         "(no matrix form: a kernel that keeps a block of "
                         "channels' states in fast memory over the "
                         "positions), the rates, dt x, D x",
    "ssm1.gate": "models/nemotron_h.py: the read-out times SiLU of the gate",
    "ssm1.out": "models/nemotron_h.py: the output projection W_out",
    "attn.gqa": "models/nemotron_h.py: an attention layer's projections, "
                "row write, attention over its slots (attend_cached inside "
                "it), wo; no rotation",
    "lmoe.down": "models/pattern.py: the projection of the normed stream "
                 "into the latent the experts work in",
    "lmoe.up": "models/pattern.py: the experts' weighted sum back out of "
               "the latent",
    "moe.shared": "models/laguna.py, models/kimi_linear.py: the shared "
                  "expert's SwiGLU; models/nemotron_h.py: its ReLU^2 unit",
    "moe_router": "models/transformer.py: the linear router and its top-k "
                  "(softmax); models/kimi_linear.py: the sigmoid router with "
                  "its selection bias",
    "moe_experts": "models/transformer.py: sort, grouped matmuls, unsort; "
                   "where a thin share of the experts is held "
                   "(`held_rows_cap`) the first sorted rows alone, added to "
                   "their tokens. How thin the layout was is two counters of "
                   "every configuration with `experts_held`: "
                   "`moe_rows_gathered` (the rows the programs gathered for "
                   "the grouped matmuls, over `moe_assignments_held`, the "
                   "real rows' that met a weight) and "
                   "`moe_calls_whole_layout` (the calls that held more than "
                   "their cap and took the whole layout); a capped program "
                   "counts both, a layout without a cap is static and the "
                   "engine reckons it from the rows its programs ran",
    "moe.zero": "models/transformer.py: the zero-compute outputs' part of an "
                "expert layer: the sum of a token's weights on them times "
                "its input. What they were chosen how often is a counter "
                "(`moe_assignments_zero`, beside `moe_assignments_held` / "
                "`_absent`; `moe_routed_most`: the most routed experts a "
                "row)",
    "scmoe.dense": "models/longcat.py: a double layer's two dense SwiGLU "
                   "MLPs",
    "loop.pass_end": "models/transformer.py: what a pass of a looped model "
                     "adds behind its layers: the model's final norm, whose "
                     "output is the next pass's input, and the exit gate on "
                     "it (a served program returns no `exit_pdf`, so the "
                     "compiler keeps the norm alone). How many passes a "
                     "program runs and how many cache layers a sequence "
                     "keeps for them: `engine_stats()['loop_steps']` / "
                     "`['kv_layers_kept']`",
    "attend_cached": "models/decoding.py: attention over the cached rows",
    "mlp": "the dense SwiGLU MLP",
    "lora": "models/transformer.py: an adapter's two matmuls",
    "lm_head": "models/decoding.py: the logits' matmul over the vocabulary",
    "sample": "models/continuous_batching.py: the decode step's sampling "
              "(the argmax; in a conditional's branches the draw and the "
              "full-vocabulary sort)",
}
