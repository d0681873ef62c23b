"""The device route assembles each batch in at most one program launch.

A batch's sources are each augmented group's output and each row served
as it is (an HBM augmented hit, an uploaded DRAM augmented hit).  The
assembled ``images`` equal, bitwise, the row-by-row stack of those
sources in slot order; labels and ids are the sampler's; a row is cut
out of a group's output only when admission votes it in, as a buffer of
its own; and the batch is never a buffer a cache tier holds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AZURE_NC96, SenecaServer
from repro.api.policies import FrequencyAdmission
from repro.data import pipeline as pl
from repro.data.pipeline import DSIPipeline
from repro.data.storage import RemoteStorage
from repro.data.synthetic import tiny
from repro.kernels.augment.ops import decode_augment_batch_seeded
from test_pipeline_spans import B, _one_batch, _pipeline

# slots: a storage miss, an HBM augmented hit, a DRAM augmented hit, ...
MIXED_IDS = [12, 8, 0, 13, 9, 1, 14, 10]
MISSES, HBM_HITS, DRAM_HITS = (12, 13, 14), (8, 9, 10), (0, 1)
# residency levels of TieredCache.residency_array
STORAGE, DRAM, HBM = 0, 2, 3
KINDS = ("cold", "hbm", "decoded", "mixed", "admitting")
LAUNCHES = {"cold": 0, "hbm": 1, "decoded": 0, "mixed": 1, "admitting": 0}


def _fixed_ids(sess, ids):
    """Serve ``ids`` as the session's next batch."""
    sess.next_batch_ids = lambda: (np.asarray(ids, np.int64), None)


def _mixed_pipeline():
    """Augmented rows of ids 0-11 admitted up front: the HBM tier holds
    four of them (8-11), DRAM the rest; ids from 12 on are misses.  The
    next batch is ``MIXED_IDS``.  Returns the pipeline, its server and
    the admitted host rows by id."""
    ds = tiny(n=32)
    server = SenecaServer.for_dataset(
        ds, hardware=AZURE_NC96, seed=1, cache_frac=0.4, use_ods=False,
        split=(0.0, 0.0, 1.0), hbm_split=(0.0, 0.0, 1.0),
        device_cache_bytes=int(4.5 * ds.augmented_bytes()))
    sess = server.open_session(batch_size=B)
    h, w = ds.crop_hw
    admitted = {}
    for sid in range(12):
        row = np.random.default_rng(sid).standard_normal(
            (h, w, 3)).astype(np.float32)
        assert sess.admit(sid, "augmented", row, row.nbytes)
        admitted[sid] = row
    level = server.service.cache.residency_array(32)
    assert [level[s] for s in HBM_HITS] == [HBM] * 3
    assert [level[s] for s in DRAM_HITS] == [DRAM] * 2
    assert [level[s] for s in MISSES] == [STORAGE] * 3
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                       executor="device", sync_refills=True)
    _fixed_ids(sess, MIXED_IDS)
    return pipe, server, admitted


def _admitting_pipeline(doorkeeper: bool = False):
    """All misses into an augmented HBM tier with room for every row, so
    that admission votes rows in.  With ``doorkeeper`` a frequency
    policy votes in only the ids of the dataset's first half: they are
    seen once before the batch, and a row needs three sightings, of
    which the route's encoded admission and its augmented vote are
    two."""
    ds = tiny(n=32)
    kw = {"admission": FrequencyAdmission(threshold=3)} if doorkeeper else {}
    server = SenecaServer.for_dataset(
        ds, hardware=AZURE_NC96, seed=1, cache_frac=0.4, use_ods=False,
        split=(0.5, 0.0, 0.5), hbm_split=(0.0, 0.0, 1.0),
        device_cache_bytes=int(1.2 * 32 * ds.augmented_bytes()), **kw)
    sess = server.open_session(batch_size=B)
    pipe = DSIPipeline(sess, RemoteStorage(ds), n_workers=2,
                       executor="device", sync_refills=True)
    if doorkeeper:
        assert not pipe.svc.admission_votes("augmented", range(16)).any()
    return pipe, server


def _make(kind):
    if kind == "mixed":
        return _mixed_pipeline()[:2]
    if kind in ("admitting", "doorkeeper"):
        return _admitting_pipeline(doorkeeper=kind == "doorkeeper")
    return _pipeline(kind)


class Recorder:
    """Wraps the route's assembly and its sampler: keeps each batch's
    sampled ids, its sources and their row-by-row stack in slot order
    (the batch as the route built it before it assembled in one
    launch)."""

    def __init__(self, monkeypatch, pipe):
        self.batches = []
        self.ids = []
        real = pl._assemble_images
        sample = pipe.session.next_batch_ids

        def assemble(groups, singles, n):
            rows = [None] * n
            for slots, out in groups:
                for i, slot in enumerate(slots):
                    rows[slot] = out[i]
            for slot, row in singles:
                rows[slot] = row
            expected = jnp.stack(rows).astype(jnp.float32)
            images, launches = real(groups, singles, n)
            self.batches.append({"groups": len(groups),
                                 "singles": len(singles),
                                 "expected": np.asarray(expected),
                                 "launches": launches})
            return images, launches

        def next_batch_ids():
            ids, forms = sample()
            self.ids.append(np.asarray(ids).copy())
            return ids, forms

        monkeypatch.setattr(pl, "_assemble_images", assemble)
        monkeypatch.setattr(pipe.session, "next_batch_ids", next_batch_ids)


def _bits(x) -> bytes:
    x = np.asarray(x)
    return x.dtype.str.encode() + str(x.shape).encode() + x.tobytes()


@pytest.mark.parametrize("kind", KINDS)
def test_images_equal_the_row_by_row_stack_of_their_sources(kind,
                                                             monkeypatch):
    pipe, server = _make(kind)
    try:
        rec = Recorder(monkeypatch, pipe)
        batch = pipe.next_batch()
        ds = pipe.ds
    finally:
        pipe.stop()
        server.close()
    [got] = rec.batches
    assert batch["images"].dtype == jnp.float32
    assert batch["images"].shape == (B, *ds.crop_hw, 3)
    assert _bits(batch["images"]) == _bits(got["expected"])
    [ids] = rec.ids
    np.testing.assert_array_equal(batch["ids"], ids)
    assert batch["ids"].dtype == np.int64
    np.testing.assert_array_equal(
        batch["labels"], [ds.label(int(s)) for s in ids])
    assert batch["labels"].dtype == np.int32
    if kind == "mixed":
        # three misses in one fused group, five rows served as they are
        assert (got["groups"], got["singles"]) == (1, 5)


def test_a_mixed_batch_holds_each_slots_own_row():
    pipe, server, admitted = _mixed_pipeline()
    try:
        batch = pipe.next_batch()
        ds, epoch = pipe.ds, pipe.session.epoch
    finally:
        pipe.stop()
        server.close()
    # the misses, fused as the route groups them: in slot order
    fused = np.asarray(decode_augment_batch_seeded(
        [ds.encoded(sid) for sid in MISSES], list(MISSES),
        np.asarray([pl._aug_seed(epoch, sid) for sid in MISSES], np.int64),
        ds_seed=pl.fused_decode_seed(ds), image_hw=ds.image_hw,
        crop_h=ds.crop_hw[0], crop_w=ds.crop_hw[1]))
    want = admitted | {sid: fused[i] for i, sid in enumerate(MISSES)}
    images = np.asarray(batch["images"])
    np.testing.assert_array_equal(batch["ids"], MIXED_IDS)
    for slot, sid in enumerate(MIXED_IDS):
        assert _bits(images[slot]) == _bits(want[sid]), (slot, sid)


@pytest.mark.parametrize("kind", KINDS)
def test_at_most_one_assembly_launch_a_batch(kind):
    """Where one group fills every slot in slot order its output is the
    batch; any other layout takes one launch."""
    pipe, server = _make(kind)
    try:
        d = _one_batch(pipe)
    finally:
        pipe.stop()
        server.close()
    assert d["batches"] == 1
    assert d["assembles"] == LAUNCHES[kind] <= 1


@pytest.mark.parametrize("kind", ["cold", "admitting", "doorkeeper",
                                  "mixed"])
def test_row_slices_count_the_rows_admission_voted_in(kind, monkeypatch):
    pipe, server = _make(kind)
    try:
        votes = []
        real = pipe.svc.admission_votes

        def admission_votes(form, sids):
            wanted = real(form, sids)
            votes.append(sum(bool(w) for w in wanted))
            return wanted

        monkeypatch.setattr(pipe.svc, "admission_votes", admission_votes)
        capacity = pipe.svc.tier_capacity("augmented")
        d = _one_batch(pipe)
    finally:
        pipe.stop()
        server.close()
    if kind == "cold":
        # no augmented capacity: no vote is asked and no row is cut
        assert capacity == 0
        assert votes == [] and d["row_slices"] == 0
    else:
        assert capacity > 0
        assert len(votes) == 1
        assert d["row_slices"] == votes[0]
    if kind == "admitting":
        assert d["row_slices"] == B
    if kind == "doorkeeper":
        assert 0 < d["row_slices"] < B
    if kind == "mixed":
        assert d["row_slices"] <= len(MISSES)


def test_an_admitted_row_equals_its_batch_row_and_outlives_the_batch():
    pipe, server = _admitting_pipeline()
    try:
        batch = pipe.next_batch()
        cache = server.service.cache
        images = batch["images"]
        held = {}
        for slot, sid in enumerate(batch["ids"].tolist()):
            form, value = cache.peek(sid)
            assert form == "augmented"
            assert isinstance(value, jax.Array)
            assert value.unsafe_buffer_pointer() != \
                images.unsafe_buffer_pointer()
            assert _bits(value) == _bits(np.asarray(images)[slot])
            held[sid] = (value, _bits(value))
        images.delete()
        del batch, images
        for sid, (value, bits) in held.items():
            assert _bits(value) == bits
            assert _bits(cache.peek(sid)[1]) == bits
    finally:
        pipe.stop()
        server.close()


def test_an_hbm_batch_is_not_a_tier_buffer():
    """Every row of the batch is an HBM hit (the warm cell's traffic):
    the batch is a new buffer, and deleting it leaves the tier whole."""
    pipe, server = _pipeline("hbm")
    try:
        cache = server.service.cache
        level = cache.residency_array(32)
        held = {sid: cache.peek(sid)[1] for sid in range(32)
                if level[sid] == HBM}
        assert len(held) >= B
        bits = {sid: _bits(v) for sid, v in held.items()}
        d_before = pipe.times.as_dict()
        batch = pipe.next_batch()
        assert pipe.times.assembles - d_before["assembles"] == 1
        images = batch["images"]
        assert all(v is not images for v in held.values())
        assert images.unsafe_buffer_pointer() not in {
            v.unsafe_buffer_pointer() for v in held.values()}
        for slot, sid in enumerate(batch["ids"].tolist()):
            assert sid in held
            assert _bits(np.asarray(images)[slot]) == bits[sid]
        images.delete()
        del batch, images
        for sid, v in held.items():
            assert _bits(v) == bits[sid]
    finally:
        pipe.stop()
        server.close()


LAYOUTS = {
    "one group in slot order": ([[0, 1, 2, 3]], [], 0),
    "one group out of slot order": ([[2, 0, 3, 1]], [], 1),
    "two groups": ([[3, 0], [1, 2]], [], 1),
    "single rows alone": ([], [1, 0, 2], 1),
    "groups and single rows": ([[4, 1], [2]], [0, 3], 1),
}


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_assembly_of_each_layout_equals_the_row_by_row_stack(layout):
    """Group outputs and single rows of distinct values, assembled and
    compared with the stack of each slot's own row."""
    group_slots, single_slots, launches = LAYOUTS[layout]
    rng = np.random.default_rng(7)

    def rows(k):
        return jnp.asarray(rng.standard_normal((k, 3, 2, 3)), jnp.float32)

    groups = [(slots, rows(len(slots))) for slots in group_slots]
    singles = [(slot, rows(1)[0]) for slot in single_slots]
    n = sum(len(s) for s in group_slots) + len(single_slots)
    by_slot = {slot: out[i] for slots, out in groups
               for i, slot in enumerate(slots)}
    by_slot |= dict(singles)
    want = jnp.stack([by_slot[s] for s in range(n)]).astype(jnp.float32)
    images, got = pl._assemble_images(groups, singles, n)
    assert got == launches
    assert _bits(images) == _bits(want)
    if launches:
        assert all(images is not out for _s, out in groups)
