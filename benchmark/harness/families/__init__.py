"""One module a model family. Everything the harness knows of a model's
shape lives in ``families/<family>.py``, and a configuration's file names
its module under the key ``family``; the rest of the harness asks it. A
later PR brings an architecture as a new module here beside its
configuration, and edits no file that is there.

A family module provides (PERF.md section 3 says what each is for):

  the plain reference   make_weights(cfg, seed, dtype) -> the family's own
                        tree; forward_logits(weights, ids, cfg, mode);
                        RefTrainer(cfg, seed, opt, store_dtype, mode) with
                        step(ids, labels) and change_sumsq(), readings
                        keyed by the names of TRAIN_PARAMS
  the program's model   serving_model(cfg, seed): the seed's weights in it;
                        training_model(cfg, mix); TRAIN_PARAMS (reading
                        name -> parameter name); load_training_weights(
                        model, weights); seed_param(cfg, key, name, dtype)
  the arithmetic        matmul_params(cfg); train_flops_per_token(cfg,
                        seq); serve_flops(cfg, positions);
                        cache_bytes_per_token(cfg), over all layers;
                        KERNEL_WORK, kernel name -> (cfg, batch, seq) ->
                        (flops, bytes) of one call
"""
from __future__ import annotations

import importlib


def of(cfg):
    """The module of the family a configuration names. There is no
    default: a configuration without the key is an error."""
    return importlib.import_module(f"{__name__}.{cfg['family']}")
