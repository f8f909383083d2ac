"""Self time of the decode program's operations under `lm_head` (the logits' matmul against the vocabulary) and `sample` (the full-vocabulary sort and the draw), per traced decode step; the two parts are said apart on stderr."""

from benchmarks import harness, readers, scope_ops


def read(ctx):
    head, sample = (scope_ops.ms_per_run(ctx, readers.DECODE_PROGRAM, (s,))
                    for s in ("lm_head", "sample"))
    if head is None and sample is None:
        return None
    harness.say("head_sample", lm_head_ms=head, sample_ms=sample)
    return (head or 0.0) + (sample or 0.0)
