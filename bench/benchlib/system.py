"""The system under test, built from the program's own functions.

The window drives the training loop as ``repro.launch.train.run``
composes it: ``image_batch_source`` with the device executor (the
pipeline's ``_next_batch_device`` and ``patchify_stub``), then the step
from ``build_train_step``, jitted with parameters and optimizer state
donated, compiled ahead of time on the first batch, and blocked on each
step's metrics as ``ResilientTrainer.run`` does.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, Optional

import numpy as np


def seeds(seed: int) -> Dict[str, int]:
    """Independent 32-bit seeds for each use, drawn from ``--seed``."""
    w = np.random.SeedSequence(int(seed)).generate_state(4, np.uint32)
    return {"params": int(w[0]), "data": int(w[1]) & 0x7FFFFFFF,
            "sampler": int(w[2]) & 0x7FFFFFFF, "sample": int(w[3])}


def model_config(config: Dict):
    """The program's ``base`` configuration with the file's overrides.  An
    override that is a dict, of a field that holds a dataclass (``{"moe":
    {"n_experts": 8}}``), replaces only the keys it names in that field."""
    from repro.configs import registry
    m = config["model"]
    base = registry.get(m["base"])
    over = {k: dataclasses.replace(getattr(base, k), **v)
            if isinstance(v, dict)
            and dataclasses.is_dataclass(getattr(base, k, None)) else v
            for k, v in m["overrides"].items()}
    return dataclasses.replace(base, **over)


def dataset(traffic: Dict, data_seed: int):
    from repro.data.synthetic import SyntheticDataset
    d = traffic["dataset"]
    return SyntheticDataset(d["name"], int(d["n_samples"]),
                            int(d["mean_encoded_bytes"]),
                            tuple(d["image_hw"]), tuple(d["crop_hw"]),
                            int(d["n_classes"]), seed=data_seed)


def optimizer(config: Dict):
    from repro.train.optimizer import AdamW, warmup_cosine
    o = config["optimizer"]
    return AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
                 schedule=warmup_cosine(o["lr"], o["warmup_steps"],
                                        o["total_steps"], o["lr_floor"]))


@dataclasses.dataclass
class System:
    model: object
    ds: object
    pipe: object
    server: object
    next_batch: Callable
    init_state: Callable
    step_fn: Callable
    batch: int
    params: object = None
    opt_state: object = None
    step: Optional[Callable] = None
    keep: Optional[Callable] = None

    def consume(self, raw: Dict) -> None:
        """The pipeline's consume hook: sees every raw batch."""
        if self.keep is not None:
            self.keep(raw, self.pipe.session.epoch)

    def close(self) -> None:
        self.pipe.stop()
        self.server.close()


def build(config: Dict, traffic: Dict, seed: int,
          step_builder: Optional[Callable] = None) -> System:
    """Model, pipeline and the uncompiled step, all from ``seed``.

    ``step_builder(model, parallel, opt)`` stands in for the program's
    ``build_train_step`` (the fault tests break the step through it)."""
    import jax

    from repro.configs.base import ParallelismConfig
    from repro.launch.train import image_batch_source
    from repro.models.model import build as build_model
    from repro.train.step import build_train_step

    s = seeds(seed)
    cfg = model_config(config)
    model = build_model(cfg)
    ds = dataset(traffic, s["data"])
    opt = optimizer(config)
    holder: Dict = {}
    next_batch, pipe, server = image_batch_source(
        model, int(config["batch"]), seed=s["sampler"], dataset=ds,
        executor="device",
        device_cache_bytes=int(traffic["device_cache_mib"]) * 2**20,
        consume_hook=lambda raw: holder["sys"].consume(raw))

    @jax.jit
    def init_state(key):
        params = model.init(key)
        return params, opt.init(params)

    parallel = ParallelismConfig(remat=config["remat"])
    step_fn = (step_builder or build_train_step)(model, parallel, opt)
    sys_ = System(model, ds, pipe, server, next_batch, init_state, step_fn,
                  int(config["batch"]))
    holder["sys"] = sys_
    return sys_


def fill_tier(sys_: System, timeout_s: float = 120.0) -> Dict:
    """One pass of ``next_batch`` over the dataset, no steps; then wait
    until every sample is resident in the HBM tier."""
    n = sys_.ds.n_samples
    for _ in range(-(-n // sys_.batch)):
        sys_.pipe.next_batch()
    deadline = time.monotonic() + timeout_s
    while True:
        resident = sys_.server.stats()["residency_counts"]["hbm"]
        if resident >= n:
            return {"resident": resident}
        if time.monotonic() > deadline:
            raise RuntimeError(f"HBM tier holds {resident} of {n} samples "
                               f"after {timeout_s} s")
        time.sleep(0.05)


def compile_step(sys_: System, first: Dict):
    import jax
    sys_.step = jax.jit(sys_.step_fn, donate_argnums=(0, 1)).lower(
        sys_.params, sys_.opt_state, first).compile()
    return sys_.step


def run_step(sys_: System, batch: Dict) -> Dict[str, float]:
    """One step as ``ResilientTrainer.run`` takes it: the compiled step,
    then every metric read back to the host, which blocks on it."""
    sys_.params, sys_.opt_state, metrics = sys_.step(
        sys_.params, sys_.opt_state, batch)
    return {k: float(v) for k, v in metrics.items()}
