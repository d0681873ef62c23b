"""The real (threaded) DSI pipeline: sampler -> fetch -> decode -> augment
-> collate -> device.

Feeds from a :class:`repro.api.Session` over the shared Seneca service
(MDP-partitioned cache + pluggable sampling/admission/eviction policies),
so the paper's concurrency experiments run for real on CPU::

    server = SenecaServer.for_dataset(ds)
    pipe = DSIPipeline(server.open_session(batch_size=32), storage)
    batch = pipe.next_batch()

Two routes (the ``executor=`` argument):

* ``"per-sample"`` (default): the host route.  Every sample runs
  lookup->fetch->decode->augment->admit inside one worker of a small
  pool, and ``next_batch`` is a synchronous barrier over the whole
  batch.  It is the VirtualClock reference of the workload runner.
* ``"device"``: device-resident preprocessing — encoded samples go
  through the fused Pallas decode+augment kernel
  (:func:`repro.kernels.augment.ops.decode_augment_batch_seeded`) in one
  launch per batch (only per-sample scalars cross the PCIe link), HBM
  cache hits serve zero-copy device arrays, and the collated
  ``"images"`` tensor is a ``jax.Array`` ready for the training step.
  Host→device payload copies (DRAM/disk hits, decoded-hit uploads) are
  metered on the telemetry ``"h2d"`` channel, which calibrates
  ``HardwareProfile.b_hbm`` — an all-HBM-hit epoch records zero bytes
  there.  Synchronous and single-threaded like ``"per-sample"``
  (VirtualClock-deterministic with ``sync_refills``); requires a
  dataset whose ``decode`` is the counter-hash
  ``SyntheticDataset.decode`` (see :func:`fused_decode_seed`).

On either route, ``start_prefetch()`` builds batches ahead of the
caller on a thread of the pipeline's own and ``get()`` takes them, in
the order ``next_batch`` would have given them, so batch n+1's storage
reads and kernel launch overlap the caller's work on batch n.

Augmentation parameters derive from per-sample seeds, not from the
route or its scheduling, so a given (epoch, sample id) gets the same
crop and flip on both routes.  Batches carry an additive ``"ids"`` key
with the sample ids in slot order.

Cache admission goes through the service's :class:`AdmissionPolicy` hooks
(capacity is voted under the cache lock, atomically with the insert) —
this module never touches cache partitions directly.

The old ``DSIPipeline(job_id, service, storage, batch_size=...)`` call
style still works as a deprecated shim that opens a session internally.
"""
from __future__ import annotations

import functools
import logging
import queue
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field, fields
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from repro.api.server import SenecaService, Session
from repro.data.augment import augment_np
from repro.data.storage import RemoteStorage
from repro.data.synthetic import SyntheticDataset

log = logging.getLogger(__name__)

EXECUTORS = ("per-sample", "device")


def _aug_seed(epoch_tag: int, sid: int) -> int:
    """The per-sample augmentation seed — shared by both routes, so
    batch composition never changes content."""
    return (epoch_tag * 1_000_003 + sid) & 0x7FFFFFFF


def fused_decode_seed(ds) -> Optional[int]:
    """The dataset's decode-PRNG seed when its ``decode`` is the
    counter-hash ``SyntheticDataset.decode`` the fused Pallas kernel
    reimplements; ``None`` for any dataset that overrides ``decode``
    (e.g. ``DecodeHeavyDataset``) — the device executor refuses those at
    construction rather than silently diverging from the host path.
    Thin lazy wrapper over :func:`repro.kernels.decode.ops` so importing
    this module never pulls in jax."""
    from repro.kernels.decode.ops import fused_decode_seed as impl
    return impl(ds)


class _Span:
    """One :meth:`StageTimes.span`: the elapsed seconds of its body on
    the pipeline's clock, added to the field ``name`` (and kept as
    ``dt``), inside a profiler annotation ``seneca.<name>``."""
    __slots__ = ("_times", "_name", "_note", "_t0", "dt")

    def __init__(self, times: "StageTimes", name: str):
        self._times, self._name = times, name
        self.dt = 0.0

    def __enter__(self) -> "_Span":
        # imported here, so that importing this module never imports jax
        from jax.profiler import TraceAnnotation
        self._note = TraceAnnotation("seneca." + self._name)
        self._note.__enter__()
        self._t0 = self._times.now()
        return self

    def __exit__(self, *exc) -> None:
        self.dt = self._times.now() - self._t0
        setattr(self._times, self._name,
                getattr(self._times, self._name) + self.dt)
        self._note.__exit__(*exc)


@dataclass
class StageTimes:
    """Seconds the pipeline spent in each stage, summed over its batches.

    ``fetch``, ``decode``, ``augment`` and ``collate`` are the executors'
    stage timers.  The device route also times its phases with
    :meth:`span` (``next_batch`` and, within it and not overlapping,
    ``sample``, ``gather``, ``fused``, ``augment``, ``rows``,
    ``admit_rows``, ``collate`` and ``upkeep``) and, inside ``gather``,
    sums per-sample counters: ``lookup`` (every tiered lookup) and
    ``admit`` (encoded admissions).  Two counts go with them:
    ``assembles`` (launches of the program that assembles a batch) and
    ``row_slices`` (rows cut out of a group's output for admission).
    ``patchify`` and ``text`` are the image feed's spans
    (``launch/train.py``).  :meth:`DSIPipeline.get` times ``wait`` (the
    caller's wait for a built batch) and counts ``taken`` (batches
    handed out) and ``ready`` (those already built when asked for).

    Each field has one writer: under :meth:`DSIPipeline.start_prefetch`
    the route's fields are written by the prefetch thread, and ``wait``,
    ``taken``, ``ready``, ``patchify`` and ``text`` by the caller."""
    fetch: float = 0.0
    decode: float = 0.0
    augment: float = 0.0
    collate: float = 0.0
    batches: int = 0
    next_batch: float = 0.0
    sample: float = 0.0
    gather: float = 0.0
    fused: float = 0.0
    rows: float = 0.0
    admit_rows: float = 0.0
    upkeep: float = 0.0
    patchify: float = 0.0
    text: float = 0.0
    wait: float = 0.0
    lookup: float = 0.0
    admit: float = 0.0
    assembles: int = 0
    row_slices: int = 0
    taken: int = 0
    ready: int = 0
    now: Callable[[], float] = field(default=time.monotonic, repr=False,
                                     compare=False)

    def span(self, name: str) -> _Span:
        """A context manager that adds its body's seconds to the field
        ``name``, under a profiler annotation ``seneca.<name>`` that
        records only while a profiler session runs."""
        if not isinstance(getattr(self, name), float):
            raise ValueError(f"StageTimes has no span field {name!r}")
        return _Span(self, name)

    def as_dict(self) -> Dict[str, float]:
        return {f.name: getattr(self, f.name) for f in fields(self)
                if f.name != "now"}


def _assemble(groups, singles, order):
    """The group outputs, then the single rows, concatenated and
    gathered by ``order`` into slot order, as float32."""
    import jax.numpy as jnp
    parts = list(groups) + ([jnp.stack(singles)] if singles else [])
    rows = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return jnp.take(rows, order, axis=0, mode="clip").astype(jnp.float32)


@functools.lru_cache(maxsize=None)
def _assemble_program():
    import jax
    return jax.jit(_assemble)


def _assemble_images(groups: List[Tuple[List[int], object]],
                     singles: List[Tuple[int, object]],
                     n: int) -> Tuple[object, int]:
    """The device route's ``(n, crop_h, crop_w, 3)`` float32 batch from
    its sources: ``groups`` of (slots, output rows in that order) and
    ``singles`` of (slot, one row).  Returns it with the number of
    programs launched: none where one group fills every slot in slot
    order (its output is the batch), else one.  A single row may be a
    cache tier's own buffer, so the batch is then always a new one."""
    if not singles and len(groups) == 1:
        slots, out = groups[0]
        if slots == list(range(n)) and out.dtype == np.float32:
            return out, 0
    order = np.empty(n, np.int32)
    pos = 0
    for slots, _out in groups:
        order[slots] = np.arange(pos, pos + len(slots))
        pos += len(slots)
    order[[slot for slot, _row in singles]] = np.arange(
        pos, pos + len(singles))
    images = _assemble_program()(tuple(out for _s, out in groups),
                                 tuple(row for _s, row in singles), order)
    return images, 1


class DSIPipeline:
    """Per-session pipeline over a shared Seneca service + RemoteStorage."""

    def __init__(self, session, storage: Optional[RemoteStorage] = None,
                 *legacy_storage, batch_size: Optional[int] = None,
                 n_workers: int = 4, prefetch: int = 2, seed: int = 0,
                 executor: str = "per-sample", consume_hook=None,
                 sync_refills: bool = False, clock=None):
        # validate before any side effect: the legacy path below
        # registers a job on the shared service, which must not leak
        # when construction fails
        if executor not in EXECUTORS:
            raise ValueError(f"unknown executor {executor!r}; expected "
                             f"one of {EXECUTORS}")
        if isinstance(session, Session):
            self.session = session
            if not isinstance(storage, RemoteStorage):
                raise TypeError("DSIPipeline(session, storage) needs a "
                                "RemoteStorage as its second argument")
        else:
            # legacy (job_id, service, storage, batch_size=...) call style
            warnings.warn(
                "DSIPipeline(job_id, service, storage, batch_size=...) is "
                "deprecated; pass a Session from "
                "SenecaServer.open_session()", DeprecationWarning,
                stacklevel=2)
            job_id, service = int(session), storage
            if len(legacy_storage) > 1 and batch_size is None:
                batch_size = legacy_storage[1]   # old positional form
            if not (isinstance(service, SenecaService) and legacy_storage
                    and batch_size):
                raise TypeError(
                    "expected DSIPipeline(session, storage) or legacy "
                    "DSIPipeline(job_id, service, storage, batch_size=N)")
            storage = legacy_storage[0]
            service.register_job(job_id, batch_size)
            self.session = Session(service, job_id, batch_size)
        self.executor = executor
        self.svc: SenecaService = self.session.service
        self.storage = storage
        self.ds: SyntheticDataset = storage.dataset
        self._fused_seed: Optional[int] = None
        if executor == "device":
            self._fused_seed = fused_decode_seed(self.ds)
            if self._fused_seed is None:
                raise ValueError(
                    "device executor needs a dataset whose decode is the "
                    "counter-hash SyntheticDataset.decode (the fused "
                    f"kernel's semantics); got {type(self.ds).__name__}")
        self.bs = self.session.batch_size
        self.pool = ThreadPoolExecutor(max_workers=n_workers)
        # pluggable time source for per-request/stage phase timestamps
        # (duck-typed Clock: .now()).  None keeps the historical wall
        # clock; a VirtualClock makes every recorded phase a *trace*
        # time — storage stalls charged through the clock-aware token
        # bucket then show up in fetch telemetry deterministically,
        # while pure-compute phases cost zero virtual seconds.
        # Host-side liveness deadlines (queue polls, thread joins) stay
        # on wall time regardless.
        self._now = time.monotonic if clock is None else clock.now
        self._clock = clock
        self.times = StageTimes(now=self._now)
        # cross-job single-flight table (service-level; None for bare
        # service doubles in tests) — consulted before producing a miss
        self._production = getattr(self.svc, "production", None)
        # telemetry feeds the adaptive repartition loop: per-stage EWMAs,
        # transfer bandwidths and per-form serve counts, aggregated
        # across every pipeline on the service
        self.telemetry = self.svc.telemetry
        self._n_workers = n_workers
        self.telemetry.add_concurrency(n_workers)
        self.rng = np.random.default_rng(seed + self.session.job_id)
        # consumer-rate hook: called with every batch ``next_batch`` or
        # ``get`` hands out, on the caller's thread, before it returns.
        # The WorkloadRunner installs a rate limiter here to emulate GPU
        # ingest (repro/workload/runner.py); anything callable works.
        self._consume_hook = consume_hook
        # deterministic mode: run background refills inline on the
        # calling thread instead of racing them on the worker pool
        # (required for byte-identical virtual-clock workload runs)
        self._sync_refills = sync_refills
        self._q: "queue.Queue" = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._prefetch_exc: Optional[BaseException] = None

    # ------------------------------------------------------------------
    def _produce_sample(self, sid: int, epoch_tag: int) -> np.ndarray:
        """Run one sample through the remaining pipeline stages."""
        t_look = self._now()
        form, value, tier = self.session.lookup_tiered(sid)
        self.telemetry.record_serve(form)
        # spill-tier hits calibrate b_disk, DRAM hits b_cache
        channel = "disk" if tier == "disk" else "cache"
        t0 = self._now()
        if form == "augmented":
            # hit cost is the lookup interval (t0 - t_look): StageTimes
            # and telemetry account the same thing (the seed charged
            # "now - t0" ~ 0 here, undercounting every hit)
            self.times.fetch += t0 - t_look
            self.telemetry.record_stage("fetch_cache", t0 - t_look)
            self.telemetry.record_bytes(channel, value.nbytes, t0 - t_look)
            return value
        if form is not None:
            # decoded/encoded hit: the lookup interval is charged here,
            # the remaining production stages in _produce_miss
            nbytes = value.nbytes if form == "decoded" else len(value)
            self.times.fetch += t0 - t_look
            self.telemetry.record_stage("fetch_cache", t0 - t_look)
            self.telemetry.record_bytes(channel, nbytes, t0 - t_look)
        prod = self._production
        if prod is None:
            return self._produce_miss(sid, epoch_tag, form, value)
        # single-flight: first misser of (sid, "augmented") leads and
        # produces; concurrent missers join and receive the result
        # zero-copy, or fall back to producing when waiting is unsafe
        while True:
            leader, flight = prod.begin(sid, "augmented")
            if leader:
                if flight is None:   # observe mode: duplicate, but live
                    return self._produce_miss(sid, epoch_tag, form, value)
                try:
                    out = self._produce_miss(sid, epoch_tag, form, value)
                except BaseException as e:
                    prod.abort(flight, e)
                    raise
                prod.finish(flight, out)
                return out
            t_j = self._now()
            ok, joined = prod.join(flight, self._clock)
            if ok:
                self.telemetry.record_coalesced(max(self._now() - t_j, 0.0))
                return joined
            if not flight.done:
                # wait declined (deterministic clock, no bound ticket)
                # or timed out on a wedged leader: produce ourselves —
                # a duplicate production, never a stall
                return self._produce_miss(sid, epoch_tag, form, value)
            # leader aborted: retry begin(); the first retrier leads

    def _produce_miss(self, sid: int, epoch_tag: int,
                      form: Optional[str], value) -> np.ndarray:
        """Remaining stages for a sample not cached in augmented form:
        fetch/decode as ``form`` requires, then augment + admit."""
        if form == "decoded":
            img = value
        elif form == "encoded":
            t1 = self._now()
            img = self.ds.decode(value, sid)
            dt = self._now() - t1
            self.times.decode += dt
            self.telemetry.record_stage("decode", dt)
            self.session.admit(sid, "decoded", img, img.nbytes)
        else:
            t0 = self._now()
            enc = self.storage.fetch(sid)
            dt = self._now() - t0
            self.times.fetch += dt
            self.telemetry.record_stage("fetch_storage", dt)
            self.telemetry.record_bytes("storage", len(enc), dt)
            self.session.admit(sid, "encoded", enc, len(enc))
            t1 = self._now()
            img = self.ds.decode(enc, sid)
            dt = self._now() - t1
            self.times.decode += dt
            self.telemetry.record_stage("decode", dt)
            self.session.admit(sid, "decoded", img, img.nbytes)
        t2 = self._now()
        out = augment_np(img, self.ds.crop_hw,
                         np.random.default_rng(_aug_seed(epoch_tag, sid)))
        dt = self._now() - t2
        self.times.augment += dt
        self.telemetry.record_stage("augment", dt)
        self.session.admit(sid, "augmented", out, out.nbytes)
        return out

    # ------------------------------------------------------------------
    def next_batch(self) -> Dict[str, np.ndarray]:
        """The next batch, built on the calling thread, then given to
        the consume hook.  Once :meth:`start_prefetch` has started its
        thread, that thread alone calls this, and :meth:`get` gives the
        hook each batch as it hands it out."""
        producer = self._thread is not None
        if producer and threading.current_thread() is not self._thread:
            raise RuntimeError("the prefetch thread runs this pipeline's "
                               "route; take its batches with get()")
        if self.executor == "device":
            batch = self._next_batch_device()
        else:
            batch = self._next_batch_host()
        if self._consume_hook is not None and not producer:
            self._consume_hook(batch)
        return batch

    def _next_batch_host(self) -> Dict[str, np.ndarray]:
        ids, _forms = self.session.next_batch_ids()
        epoch_tag = self.session.epoch
        imgs = list(self.pool.map(
            lambda s: self._produce_sample(int(s), epoch_tag), ids))
        t0 = self._now()
        batch = {
            "images": np.stack(imgs).astype(np.float32),
            "labels": np.asarray([self.ds.label(int(s)) for s in ids],
                                 np.int32),
            "ids": np.asarray(ids, np.int64),
        }
        dt = self._now() - t0
        self.times.collate += dt
        self.telemetry.record_stage("collate", dt, n=len(ids))
        self.times.batches += 1
        self._process_refills()
        # adaptive-repartition tick: a fast no-op in "static"/"on-change"
        # modes; in "adaptive" this is where calibrated drift is checked
        self.svc.maybe_repartition()
        return batch

    def _next_batch_device(self) -> Dict[str, np.ndarray]:
        """One batch through the device route: fused decode+augment for
        encoded samples, zero-copy serve for HBM hits, device collate.

        Each group's output and each single row (an HBM hit, or an
        uploaded DRAM/disk hit) is a source of the batch, assembled in
        slot order by at most one program launch
        (:func:`_assemble_images`); the only host→device payload traffic
        (metered on the ``"h2d"`` channel) is DRAM/disk-cached values
        being uploaded.  Encoded samples never materialize a host
        decoded image — the fused kernel ships per-sample scalars only —
        so (by design) this route admits no "decoded" forms.

        Telemetry timings block on JAX async dispatch
        (``block_until_ready``) before the closing timestamp — otherwise
        the h2d EWMA feeding the CALIBRATABLE ``b_hbm`` and the fused
        stage times would measure dispatch latency, not the transfer or
        compute, and mis-steer MDP repartitioning.

        The whole call and each of its phases run in a span of
        :class:`StageTimes` (``self.times``); the per-sample lookups and
        encoded admissions are its counters.
        """
        import jax
        import jax.numpy as jnp

        from repro.kernels.augment.ops import (augment_batch_seeded,
                                               decode_augment_batch_seeded)
        tel, times = self.telemetry, self.times
        with times.span("next_batch"):
            with times.span("sample"):
                ids, _forms = self.session.next_batch_ids()
            epoch_tag = self.session.epoch
            # the batch's sources: (slots, output) of each augmented
            # group and (slot, row) of each row served as it is
            groups: List[Tuple[List[int], object]] = []
            singles: List[Tuple[int, object]] = []
            # (slot, sid, value): encoded payloads, host decoded images
            # and HBM decoded images, each augmented in one launch
            enc_group: List[Tuple[int, int, bytes]] = []
            dec_group: List[Tuple[int, int, np.ndarray]] = []
            dec_dev_group: List[Tuple[int, int, object]] = []
            with times.span("gather"):
                for slot, sid_ in enumerate(ids):
                    sid = int(sid_)
                    t_look = self._now()
                    form, value, tier = self.session.lookup_tiered(sid)
                    tel.record_serve(form)
                    t0 = self._now()
                    times.lookup += t0 - t_look
                    if form is None:
                        enc = self.storage.fetch(sid)
                        t1 = self._now()
                        self.session.admit(sid, "encoded", enc, len(enc))
                        times.admit += self._now() - t1
                        dt = t1 - t0
                        times.fetch += dt
                        tel.record_stage("fetch_storage", dt)
                        tel.record_bytes("storage", len(enc), dt)
                        enc_group.append((slot, sid, enc))
                        continue
                    times.fetch += t0 - t_look
                    tel.record_stage("fetch_cache", t0 - t_look)
                    if form == "augmented" and tier == "hbm":
                        # zero-copy device serve: no h2d traffic at all
                        singles.append((slot, value))
                        continue
                    channel = "disk" if tier == "disk" else "cache"
                    if form == "augmented":
                        host = np.asarray(value)
                        tel.record_bytes(channel, host.nbytes, t0 - t_look)
                        t1 = self._now()
                        singles.append(
                            (slot, jax.block_until_ready(jnp.asarray(host))))
                        tel.record_bytes("h2d", host.nbytes,
                                         self._now() - t1)
                    elif form == "decoded":
                        if tier == "hbm":
                            # device-resident decoded hit: augment on
                            # device — no host round-trip, so no
                            # byte-channel record (a d2h download
                            # metered as "cache" would skew b_cache)
                            dec_dev_group.append((slot, sid, value))
                        else:
                            img = np.asarray(value)
                            tel.record_bytes(channel, img.nbytes,
                                             t0 - t_look)
                            dec_group.append((slot, sid, img))
                    else:                              # encoded cache hit
                        tel.record_bytes(channel, len(value), t0 - t_look)
                        enc_group.append((slot, sid, value))
            # (sid, group output, row): rows a group augmented afresh
            fresh: List[Tuple[int, object, int]] = []

            def place(group, out):
                with times.span("rows"):
                    groups.append(([slot for slot, _sid, _v in group], out))
                    fresh.extend((sid, out, i)
                                 for i, (_slot, sid, _v) in enumerate(group))

            if enc_group:
                with times.span("fused"):
                    sids = [sid for _s, sid, _p in enc_group]
                    seeds = np.asarray(
                        [_aug_seed(epoch_tag, sid) for sid in sids], np.int64)
                    t1 = self._now()
                    out = jax.block_until_ready(decode_augment_batch_seeded(
                        [p for _s, _sid, p in enc_group], sids, seeds,
                        ds_seed=self._fused_seed, image_hw=self.ds.image_hw,
                        crop_h=self.ds.crop_hw[0], crop_w=self.ds.crop_hw[1]))
                    dt = self._now() - t1
                # one fused launch covers both stages; split the kernel
                # call's time evenly so the calibrated
                # t_da = conc/(decode+augment) lands on the fused rate
                tel.record_stage("decode", dt / 2, n=len(enc_group))
                tel.record_stage("augment", dt / 2, n=len(enc_group))
                place(enc_group, out)
            # an augment span holds its group's stacking and seeds too;
            # telemetry gets the kernel call alone
            if dec_group:
                with times.span("augment"):
                    sids = [sid for _s, sid, _img in dec_group]
                    imgs = np.stack([img for _s, _sid, img in dec_group])
                    seeds = np.asarray(
                        [_aug_seed(epoch_tag, sid) for sid in sids], np.int64)
                    t1 = self._now()
                    out = jax.block_until_ready(
                        augment_batch_seeded(imgs, seeds, *self.ds.crop_hw,
                                             as_device=True))
                    dt = self._now() - t1
                tel.record_stage("augment", dt, n=len(dec_group))
                # decoded pixels shipped up for the device-side augment
                tel.record_bytes("h2d", imgs.nbytes, dt)
                place(dec_group, out)
            if dec_dev_group:
                with times.span("augment"):
                    sids = [sid for _s, sid, _img in dec_dev_group]
                    imgs_dev = jnp.stack(
                        [img for _s, _sid, img in dec_dev_group])
                    seeds = np.asarray(
                        [_aug_seed(epoch_tag, sid) for sid in sids], np.int64)
                    t1 = self._now()
                    out = jax.block_until_ready(
                        augment_batch_seeded(imgs_dev, seeds,
                                             *self.ds.crop_hw,
                                             as_device=True))
                    dt = self._now() - t1
                # pixels were already device-resident: no h2d traffic
                tel.record_stage("augment", dt, n=len(dec_dev_group))
                place(dec_dev_group, out)
            # admit the freshly augmented rows admission votes in, each
            # cut out of its group's output as a buffer of its own:
            # HBM-first put routing keeps them device-resident; without
            # a device tier admit host copies so a DRAM slot never pins
            # a jax buffer
            with times.span("admit_rows"):
                if fresh and self.svc.tier_capacity("augmented") > 0:
                    wanted = self.svc.admission_votes(
                        "augmented", [sid for sid, _o, _i in fresh])
                    entries = []
                    for (sid, out, i), w in zip(fresh, wanted):
                        if w:
                            row = out[i]
                            entries.append(
                                (sid, row if self.svc.has_hbm
                                 else np.asarray(row), int(row.nbytes)))
                    times.row_slices += len(entries)
                    if entries:
                        self.session.admit_batch("augmented", entries)
            with times.span("collate") as collate:
                images, launches = _assemble_images(groups, singles,
                                                    len(ids))
                batch = {
                    "images": images,
                    "labels": np.asarray(
                        [self.ds.label(int(s)) for s in ids], np.int32),
                    "ids": np.asarray(ids, np.int64),
                }
            tel.record_stage("collate", collate.dt, n=len(ids))
            times.assembles += launches
            times.batches += 1
            with times.span("upkeep"):
                self._process_refills()
                self.svc.maybe_repartition()
        return batch

    def _process_refills(self, max_n: int = 32) -> None:
        """ODS step 5: repopulate evicted augmented slots with *fresh*
        random samples (unseen by every job), on the worker pool — the
        paper's background-refill thread.  Also proactively tops up free
        augmented capacity (cold start)."""
        work = self.svc.take_refill_work(max_n)
        spare = max_n - len(work)
        if spare > 0 and self.svc.tier_capacity("augmented"):
            free_slots = self.svc.tier_free_bytes("augmented") \
                // max(self.ds.augmented_bytes(), 1)
            if free_slots > 0:
                extra = self.svc.refill_candidates(min(spare, free_slots))
                work = np.concatenate([work, extra]) if len(work) else extra
        for sid in work:
            if self._sync_refills:
                self._refill_one(int(sid))
            else:
                self.pool.submit(self._refill_one, int(sid))

    def _refill_one(self, sid: int) -> None:
        flight = None
        prod = self._production
        try:
            # a raced refill/admit may already have repopulated this
            # slot; form_of() is stats-neutral and containment-only, so
            # the check neither inflates misses nor reads a spilled
            # payload off disk just to learn the form
            if self.svc.cache.form_of(sid) == "augmented":
                return
            if prod is not None:
                leader, fl = prod.begin(sid, "augmented")
                if not leader:
                    # a foreground production of this id is already in
                    # flight and will admit the augmented form itself —
                    # the refill would be pure duplicate work
                    return
                flight = fl
            enc = self.storage.fetch(sid)
            img = self.ds.decode(enc, sid)
            out = augment_np(img, self.ds.crop_hw,
                             np.random.default_rng(sid ^ 0x5EED))
            self.session.admit(sid, "augmented", out, out.nbytes)
            if prod is not None:
                prod.finish(flight, out)
                flight = None
        except Exception:      # background worker must never kill serving
            if prod is not None and flight is not None:
                prod.abort(flight)   # wake joiners; the first retries
            # ... but it must not fail silently either: count every
            # failure (stats()["refill_errors"]) and log the first
            if self.telemetry.record_error("refill") == 1:
                log.warning(
                    "background refill failed for sample %d (first "
                    "occurrence; later failures only counted in "
                    "stats()['refill_errors'])", sid, exc_info=True)

    # ------------------------------------------------------------------
    def start_prefetch(self) -> None:
        """Build batches ahead of the caller, up to ``prefetch`` queued
        and one more held, on a thread of the pipeline's own; take them
        with :meth:`get`.  A second call finds the thread running and
        does nothing.  From then on only that thread runs the route:
        :meth:`next_batch` from any other thread is an error."""
        if self._thread is not None:
            return

        def run():
            batch = None
            while not self._stop.is_set():
                if batch is None:
                    try:
                        batch = self.next_batch()
                    except Exception as e:   # noqa: BLE001
                        # record (don't silently die): get() re-raises
                        self._prefetch_exc = e
                        if self.telemetry.record_error("prefetch") == 1:
                            log.warning("prefetch thread failed in "
                                        "next_batch()", exc_info=True)
                        return
                try:
                    self._q.put(batch, timeout=0.5)
                except queue.Full:
                    # consumer is slow: hold the built batch and re-offer
                    # it (the seed rebuilt a fresh batch here, silently
                    # dropping this one's sample ids and wasting the work)
                    continue
                batch = None
        self._thread = threading.Thread(target=run, daemon=True,
                                        name="seneca-prefetch")
        self._thread.start()

    def get(self, timeout: Optional[float] = 60.0
            ) -> Dict[str, np.ndarray]:
        """The next batch :meth:`start_prefetch` built, in the order
        :meth:`next_batch` would have given them.  ``timeout=None``
        waits as long as the prefetch thread lives (a slow build is
        backpressure, as in ``next_batch``); a finite timeout raises
        ``queue.Empty`` at the deadline.  A prefetch thread that failed
        re-raises its exception here, chained.  The wait is the span
        ``wait``; ``taken`` counts the batches handed out and ``ready``
        those already queued when asked for.  The consume hook fires
        here, on the caller's thread, with each batch handed out."""
        with self.times.span("wait"):
            ready = not self._q.empty()
            batch = self._take(timeout)
        self.times.taken += 1
        self.times.ready += ready
        if self._consume_hook is not None:
            self._consume_hook(batch)
        return batch

    def _take(self, timeout: Optional[float]) -> Dict[str, np.ndarray]:
        deadline = float("inf") if timeout is None \
            else time.monotonic() + timeout
        while True:
            # cap the poll at the remaining deadline (sub-poll timeouts
            # must not overshoot by a whole 0.2 s quantum)
            wait = min(0.2, deadline - time.monotonic())
            try:
                return self._q.get(timeout=max(wait, 0.0))
            except queue.Empty:
                if self._prefetch_exc is not None:
                    raise RuntimeError(
                        "prefetch thread died; no more batches are "
                        "coming") from self._prefetch_exc
                if timeout is None and (self._thread is None
                                        or not self._thread.is_alive()):
                    raise RuntimeError(
                        "no prefetch thread is running: call "
                        "start_prefetch() first, and not after stop()")
                if time.monotonic() >= deadline:
                    raise

    def stop(self, close_session: bool = True) -> None:
        """Tear the pipeline down.  ``close_session=False`` keeps the
        session (and its sampler state) alive — the fault-recovery path
        rebuilds a fresh pipeline on the surviving session after a
        worker crash or around a preemption."""
        if not self._stop.is_set():
            self.telemetry.remove_concurrency(self._n_workers)
        self._stop.set()
        if self._thread:
            # it ends after the batch in hand: join before the session
            # closes under it
            self._thread.join()
        self.pool.shutdown(wait=False)
        if close_session:
            self.session.close()
