"""ODS metadata backends: one protocol, a NumPy engine and a JAX twin.

The seed shipped two disconnected ODS implementations — the NumPy
:class:`repro.core.ods.ODSState` driving the live service and the jittable
:mod:`repro.core.ods_jax` kernel — with no shared interface.  This module
gives them one: :class:`OdsBackend` is everything the server/sampler layer
needs (job registry, batch substitution, status bookkeeping, admission
value, stats), and ``SenecaServer(backend="jax")`` swaps the fused
``substitute_jit`` path in behind the same session API.

Documented equivalence level (pinned by tests/test_api.py): the two
backends agree on the ODS *invariants* — each job sees every sample once
per epoch, cached-unseen samples are preferred over storage fetches, and
augmented entries evict at refcount == threshold — not on which random
cached sample fills a given slot (the JAX kernel ranks candidates with a
fold-in PRNG instead of ``Generator.choice``; see ods_jax's module doc).

The JAX adapter keeps the authoritative metadata on host (admissions and
evictions arrive from cache worker threads between batches) and stages it
onto the device per substitution call; at real scale the state would live
device-resident behind a donate/update loop, which the protocol already
permits.
"""
from __future__ import annotations

from typing import Dict, Optional, Protocol, Tuple, runtime_checkable

import numpy as np

from repro.core.ods import AUGMENTED, IN_STORAGE, ODSState

__all__ = ["OdsBackend", "NumpyOdsBackend", "JaxOdsBackend",
           "NO_REFCOUNT_EVICT",
           "register_backend", "resolve_backend", "backend_names"]


@runtime_checkable
class OdsBackend(Protocol):
    """Metadata + substitution engine behind a SenecaServer."""

    name: str
    n_samples: int

    # job registry -----------------------------------------------------
    def register_job(self, job_id: int) -> None: ...
    def unregister_job(self, job_id: int) -> None: ...
    @property
    def n_jobs(self) -> int: ...

    # sampling ---------------------------------------------------------
    def sample_batch(self, job_id: int, requested: np.ndarray,
                     evict_threshold: Optional[int] = None
                     ) -> Tuple[np.ndarray, np.ndarray]: ...
    def count_serve(self, batch: np.ndarray) -> None: ...
    def epoch_of(self, job_id: int) -> int: ...

    # cache bookkeeping ------------------------------------------------
    def status_of(self, ids: np.ndarray) -> np.ndarray: ...
    def mark_cached(self, ids: np.ndarray, form: int) -> None: ...
    def mark_evicted(self, ids: np.ndarray) -> None: ...
    def set_residency(self, levels: Optional[np.ndarray]) -> None: ...
    def set_inflight(self, mask: Optional[np.ndarray]) -> None: ...
    def admission_value(self, sample_id: int) -> int: ...
    def storage_pool(self) -> np.ndarray: ...

    # fault tolerance --------------------------------------------------
    def checkpoint_job(self, job_id: int) -> Dict: ...
    def restore_job(self, job_id: int, snap: Dict) -> None: ...

    # stats ------------------------------------------------------------
    @property
    def hits(self) -> int: ...
    @property
    def misses(self) -> int: ...
    @property
    def substitutions(self) -> int: ...
    def hit_rate(self) -> float: ...
    def metadata_bytes(self) -> int: ...


class NumpyOdsBackend:
    """Thin adapter over the vectorized NumPy ODS (the seed engine)."""

    name = "numpy"

    def __init__(self, n_samples: int, seed: int = 0):
        self.state = ODSState.create(n_samples, seed=seed)
        self.n_samples = n_samples

    # job registry -----------------------------------------------------
    def register_job(self, job_id):
        self.state.register_job(job_id)

    def unregister_job(self, job_id):
        self.state.unregister_job(job_id)

    @property
    def n_jobs(self):
        return self.state.n_jobs

    # sampling ---------------------------------------------------------
    def sample_batch(self, job_id, requested, evict_threshold=None):
        return self.state.sample_batch(job_id, requested,
                                       evict_threshold=evict_threshold)

    def count_serve(self, batch):
        cached = self.state.status[batch] != IN_STORAGE
        self.state.hits += int(cached.sum())
        self.state.misses += int(len(batch) - cached.sum())

    def epoch_of(self, job_id):
        return self.state.epoch.get(job_id, 0)

    # cache bookkeeping ------------------------------------------------
    def status_of(self, ids):
        return self.state.status[ids].copy()

    def mark_cached(self, ids, form):
        self.state.mark_cached(np.asarray(ids), form)

    def mark_evicted(self, ids):
        self.state.mark_evicted(np.asarray(ids))

    def set_residency(self, levels):
        self.state.set_residency(levels)

    def set_inflight(self, mask):
        self.state.set_inflight(mask)

    def admission_value(self, sample_id):
        return self.state.admission_value(sample_id)

    def storage_pool(self):
        return np.flatnonzero(self.state.status == IN_STORAGE)

    # fault tolerance --------------------------------------------------
    def checkpoint_job(self, job_id):
        return self.state.checkpoint_job(job_id)

    def restore_job(self, job_id, snap):
        self.state.restore_job(job_id, snap)

    # stats ------------------------------------------------------------
    @property
    def hits(self):
        return self.state.hits

    @property
    def misses(self):
        return self.state.misses

    @property
    def substitutions(self):
        return self.state.substitutions

    def hit_rate(self):
        return self.state.hit_rate()

    def metadata_bytes(self):
        return self.state.metadata_bytes()


# threshold meaning "never evict on refcount"; for the jit'd kernel it is
# a static argument, so the sentinel compiles once
NO_REFCOUNT_EVICT = 1 << 30


class JaxOdsBackend:
    """Runs batch substitution through the fused ``ods_jax.substitute_jit``
    kernel while keeping per-job seen/served/epoch plus the shared
    status/refcount tables authoritative on host."""

    name = "jax"

    def __init__(self, n_samples: int, seed: int = 0):
        import jax  # the repo's toolchain bakes jax in; fail loud if not
        self._jax = jax
        from repro.core import ods_jax
        self._ods_jax = ods_jax
        self.n_samples = n_samples
        self.status = np.zeros(n_samples, np.uint8)
        self.refcount = np.zeros(n_samples, np.int32)
        self.seen: Dict[int, np.ndarray] = {}
        self.served: Dict[int, int] = {}
        self.epoch: Dict[int, int] = {}
        self._key = jax.random.key(seed)
        self._residency: Optional[np.ndarray] = None
        self._inflight: Optional[np.ndarray] = None
        self._hits = 0
        self._misses = 0
        self._substitutions = 0

    # job registry -----------------------------------------------------
    def register_job(self, job_id):
        self.seen[job_id] = np.zeros(self.n_samples, bool)
        self.served[job_id] = 0
        self.epoch[job_id] = 0

    def unregister_job(self, job_id):
        self.seen.pop(job_id, None)
        self.served.pop(job_id, None)
        self.epoch.pop(job_id, None)

    @property
    def n_jobs(self):
        return max(len(self.seen), 1)

    # sampling ---------------------------------------------------------
    def sample_batch(self, job_id, requested, evict_threshold=None):
        import jax.numpy as jnp
        thr = int(evict_threshold) if evict_threshold is not None \
            else self.n_jobs
        requested = np.asarray(requested)
        B = len(requested)
        # mirror the kernel's rollover predicate so host epoch counting
        # stays in lockstep with the device-side seen/served reset
        if self.n_samples - self.served[job_id] < B:
            self.epoch[job_id] += 1
        pre_status = self.status
        pre_seen = self.seen[job_id]
        state = self._ods_jax.ODSJaxState(
            status=jnp.asarray(self.status),
            refcount=jnp.asarray(self.refcount),
            seen=jnp.asarray(pre_seen),
            served=jnp.asarray(self.served[job_id], jnp.int32))
        self._key, sub = self._jax.random.split(self._key)
        # the coalescing table's in-flight mask routes to separate
        # jitted variants; with the mask absent (coalescing off or
        # table idle) the historical kernels — and their exact draw
        # sequences — run untouched
        infl = self._inflight
        if infl is not None and not infl.any():
            infl = None
        if self._residency is not None:
            # two-level cache: the residency-ranked kernel (DRAM-unseen
            # candidates outrank disk-unseen ones outrank storage)
            if infl is not None:
                state, batch, evict_mask = \
                    self._ods_jax.substitute_tiered_inflight_jit(
                        state, jnp.asarray(requested), sub, thr,
                        jnp.asarray(self._residency), jnp.asarray(infl))
            else:
                state, batch, evict_mask = \
                    self._ods_jax.substitute_tiered_jit(
                        state, jnp.asarray(requested), sub, thr,
                        jnp.asarray(self._residency))
        elif infl is not None:
            state, batch, evict_mask = self._ods_jax.substitute_inflight_jit(
                state, jnp.asarray(requested), sub, thr, jnp.asarray(infl))
        else:
            state, batch, evict_mask = self._ods_jax.substitute_jit(
                state, jnp.asarray(requested), sub, thr)
        batch = np.asarray(batch)
        cached = pre_status[batch] != IN_STORAGE
        self._hits += int(cached.sum())
        self._misses += int(B - cached.sum())
        direct = (pre_status[requested] != IN_STORAGE) & ~pre_seen[requested]
        self._substitutions += int(np.count_nonzero(
            ~direct & (pre_status[requested] == IN_STORAGE) & cached))
        # np.array (not asarray): device buffers view as read-only, and the
        # host copies take writes from mark_cached / mark_evicted
        self.status = np.array(state.status)
        self.refcount = np.array(state.refcount)
        self.seen[job_id] = np.array(state.seen)
        self.served[job_id] = int(state.served)
        return batch, np.flatnonzero(np.asarray(evict_mask))

    def count_serve(self, batch):
        cached = self.status[batch] != IN_STORAGE
        self._hits += int(cached.sum())
        self._misses += int(len(batch) - cached.sum())

    def epoch_of(self, job_id):
        return self.epoch.get(job_id, 0)

    # cache bookkeeping ------------------------------------------------
    def status_of(self, ids):
        return self.status[ids].copy()

    def mark_cached(self, ids, form):
        ids = np.asarray(ids)
        self.status[ids] = form
        if form == AUGMENTED:
            # same semantics as ODSState.mark_cached: start the refcount at
            # the number of jobs that already consumed the sample so the
            # threshold still fires after the remaining jobs use it
            count = np.zeros(len(ids), np.int32)
            for bits in self.seen.values():
                count += bits[ids].astype(np.int32)
            self.refcount[ids] = count

    def mark_evicted(self, ids):
        ids = np.asarray(ids)
        self.status[ids] = IN_STORAGE
        self.refcount[ids] = 0

    def set_residency(self, levels):
        self._residency = levels

    def set_inflight(self, mask):
        self._inflight = mask

    def admission_value(self, sample_id):
        return self.n_jobs - int(sum(bits[sample_id]
                                     for bits in self.seen.values()))

    def storage_pool(self):
        return np.flatnonzero(self.status == IN_STORAGE)

    # fault tolerance --------------------------------------------------
    def checkpoint_job(self, job_id):
        """Same contract as :meth:`ODSState.checkpoint_job` — seen mask,
        epoch, served; the fold-in key is recorded for inspection but
        not restored (it is shared across jobs)."""
        if job_id not in self.seen:
            raise KeyError(f"job {job_id} is not registered")
        return {
            "n_samples": self.n_samples,
            "seen": np.packbits(self.seen[job_id]),
            "epoch": int(self.epoch[job_id]),
            "served": int(self.served[job_id]),
            "substitutions": int(self._substitutions),
            "rng_state": np.asarray(
                self._jax.random.key_data(self._key)).tolist(),
        }

    def restore_job(self, job_id, snap):
        if int(snap["n_samples"]) != self.n_samples:
            raise ValueError(
                f"snapshot is for a {snap['n_samples']}-sample dataset, "
                f"this one has {self.n_samples}")
        if job_id not in self.seen:
            raise KeyError(f"job {job_id} is not registered")
        self.seen[job_id] = np.unpackbits(
            np.asarray(snap["seen"], np.uint8),
            count=self.n_samples).astype(bool)
        self.epoch[job_id] = int(snap["epoch"])
        self.served[job_id] = int(snap["served"])

    # stats ------------------------------------------------------------
    @property
    def hits(self):
        return self._hits

    @property
    def misses(self):
        return self._misses

    @property
    def substitutions(self):
        return self._substitutions

    def hit_rate(self):
        total = self._hits + self._misses
        return self._hits / total if total else 0.0

    def metadata_bytes(self):
        return self.n_samples * len(self.seen) // 8 + self.n_samples


_BACKENDS: Dict[str, type] = {"numpy": NumpyOdsBackend, "jax": JaxOdsBackend}


def register_backend(name: str, factory: type) -> None:
    _BACKENDS[name] = factory


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_BACKENDS))


def resolve_backend(spec, n_samples: int, seed: int = 0):
    """Name or instance -> OdsBackend for ``n_samples``."""
    if isinstance(spec, str):
        try:
            return _BACKENDS[spec](n_samples, seed=seed)
        except KeyError:
            raise ValueError(f"unknown ODS backend {spec!r}; registered: "
                             f"{backend_names()}") from None
    if not isinstance(spec, OdsBackend):
        raise TypeError(f"{spec!r} does not implement OdsBackend")
    return spec
