"""Model families: one module per architecture, `bench/families/<name>.py`,
chosen by a configuration file's `reference` key (the key that also picks
the plain reference, `bench/references/<name>.py`).

A family module gives:

- `model_config(name, hf)`: the engine's `repro.nn.config.ModelConfig`
  for the configuration's published keys `hf`;
- `leaves(hf)`: every canonical weight as `(name, shape, kind)`, in the
  order whose index seeds it (`bench/weights.py` names the kinds);
- `engine_params(w, model_cfg)`: the engine's parameter tree holding the
  canonical arrays `w` themselves (`bench/weights.engine_params` checks it
  against the tree the engine builds);
- `Shape.from_config(hf)`: the yardstick the device metrics are measured
  against, with `n_layers`, `params`, `decode_token_flops(pos)`,
  `prefill_chunk_flops(start, n)`, `paged_attention_call(positions, page)`
  (bytes and flops of one layer's decode attention call) and
  `pool_dims(pages, page)` (the dims of every K/V page pool the decode
  program holds, stacked over layers and for one layer).

A new family is then a new file here, beside its reference, its
configuration and its cells.
"""
from __future__ import annotations

import importlib


def of(conf: dict):
    """The family module that configuration `conf` names."""
    return importlib.import_module(f"bench.families.{conf['reference']}")


def shape(conf: dict):
    """The family's yardstick for configuration `conf`."""
    return of(conf).Shape.from_config(conf["config"])
