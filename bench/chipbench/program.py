"""What the drivers share: the program's configuration object built from a
configuration file, and the cell's data made from the seed."""
from __future__ import annotations

from typing import Any, Dict

import jax

from chipbench import datagen


def dsekl_config(conf: Dict[str, Any], n_train: int):
    from repro.core import DSEKLConfig

    return DSEKLConfig(
        n_grad=conf["n_grad"], n_expand=conf["n_expand"],
        kernel=conf["kernel"], kernel_params=(("gamma", conf["gamma"]),),
        loss=conf["loss"], lam=conf["lam_times_n"] / n_train,
        lr0=conf["lr0"], schedule=conf["schedule"], impl=conf["impl"],
        fuse_dual_pass=conf["fuse_dual_pass"])


def seed_key(seed: int, purpose: int):
    """An independent key per purpose: 1 data, 2 fits, 3 alpha.  Seeds may
    pass 32 bits: the high part is folded in."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF), purpose)


def rows(conf: Dict[str, Any], seed: int, n: int, stream: int):
    """``n`` rows of the configuration's distribution for this seed;
    stream 0 is the training set, other streams are further rows of the
    same distribution (validation, queries)."""
    gen = datagen.GENERATORS[conf["generator"]]
    return gen(seed_key(seed, 1), n=n, d=conf["n_features"], stream=stream)
