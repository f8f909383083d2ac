"""The table of model families: the ONE place that says which module runs
a configuration. The rest asks `of(cfg)` and names no family; a module is
imported at its first lookup, so a process that trains or serves the one
block imports no other. A family states what it is in its own module, under
the same names in every family (a module protocol: no base class):
`FIELDS`, the `TransformerConfig` fields that are its own (a field neither
in `transformer.COMMON` nor its family's is refused by name where it is set:
no family lists another's); `check(cfg)`, what it needs of them;
`kept(cfg, max_len)`, what its layers keep a sequence, `Kept`s in `KVCache`'s
field order, stated ONCE (`cfg.keeps` / `.stateful` / `.full_layers` ...,
`init_cache`, `_kv_rows` and `only_kv_rows` read it); a pattern its `leaves`
(behind `pattern.py`'s `init_params` / `param_axes` / `num_params`) and ONE
layer of a kind, `layer(cfg, call, kind, i, n, carry)` over the `KVCache`
fields `CARRIED` (behind `pattern.forward_cached`, the one loop over a
pattern's layers: Laguna, Kimi-Linear and Nemotron-H state no
`forward_cached`; LongCat, whose one scan of double layers counts five
things, keeps its own); the one block's sublayers `extra_params`,
`init_block_params`, `update_block_axes`, `attention_cached`, `router`.
A new family is its module, one line of a table here, its fields, a
`KVCache` field only for a new kind of thing, its scopes and its tests.
"""

from __future__ import annotations

import importlib
from typing import Any, NamedTuple, Optional, Tuple

# module under `ray_tpu.models` -> what selects it: a layer pattern
# (`layer_kinds`) by the kinds its layers are of, lead and tail among them.
# The FIRST family whose kinds hold the pattern's runs it, so a kind has one
# name in one family: grouped attention over K/V rows is "gqa" as one of
# `nemotron_h`'s one-sublayer layers and "gkv" as the attention half of a
# `kimi_linear` layer (delta-rule layers beside latent rows, "mla", or beside
# K/V rows, "gkv");
PATTERNS = {
    "longcat": {"scmoe"},
    "kimi_linear": {"kda", "mla", "gkv"},
    "laguna": {"window", "full"},
    "nemotron_h": {"ssm", "ssm1", "gqa", "mlp", "lmoe"},
}
# the one block (`transformer.py` + `decoding.py`) by the sublayers it is given
SUBLAYERS = {"zaya": {"attention": "cca", "router": "zaya_mlp"}}
ONE_BLOCK = "transformer"


class Kept(NamedTuple):
    """`KVCache` fields of one shape that a family's layers keep a sequence,
    [layers, slots, rows, *shape] each (`decoding.ROWS`); `rows` None: a
    STATE [layers, slots, *shape], rewritten whole by every step."""
    fields: Tuple[str, ...]
    layers: int
    rows: Optional[int]  # a slot's: the cache's `max_len`, or a ring's window
    shape: Tuple[int, ...]  # of one row, or of the state
    dtype: Any = None  # None: the cache's
    # one a field where the fields' rows differ (keys wider than the values,
    # `shape` then the widest); (): `shape` is every field's
    shapes: Tuple[Tuple[int, ...], ...] = ()

    def shape_of(self, field: str) -> Tuple[int, ...]:
        return self.shapes[self.fields.index(field)] if self.shapes \
            else self.shape


def of(cfg):
    """The module of `cfg`'s family."""
    name = ONE_BLOCK
    if cfg.layer_kinds:
        kinds = {cfg.lead_kind, *cfg.layer_kinds, *cfg.tail_kinds} - {""}
        name = next((n for n, runs in PATTERNS.items() if kinds <= runs),
                    None)
        if name is None:
            raise ValueError(
                f"unknown layer kinds {sorted(kinds)!r} (layer_kinds, "
                "lead_kind, tail_kinds): a pattern is of the kinds of ONE of "
                f"{PATTERNS!r}")
    else:
        for module, selects in SUBLAYERS.items():
            if any(getattr(cfg, f) == v for f, v in selects.items()):
                name = module
    return importlib.import_module(f"ray_tpu.models.{name}")


def only_kv_rows(cfg, holder: str, also: Tuple[str, ...] = ()) -> None:
    """Refuse `cfg` for a cache of K/V rows (and the fields `also`) alone if
    a sequence keeps anything else, whichever family it is; `holder` ends
    the sentence: what holds none of it, and where to turn."""
    beside = [n for n in cfg.keeps if n not in ("k", "v", *also)]
    if beside:
        raise ValueError(f"a sequence keeps {', '.join(beside)} beside or in "
                         f"place of its K/V rows, and {holder}")
