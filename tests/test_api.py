"""repro.api facade: sessions, pluggable policies, backend parity.

The parity tests pin the documented invariant-level equivalence between
the NumPy ODS and the jittable JAX twin behind the same session API:
both prefer cached-unseen samples over storage fetches, both serve every
sample exactly once per job per epoch, and both evict augmented entries
at refcount == n_jobs — they do NOT agree on which random cached sample
fills a given slot (different PRNG mechanics, see ods_jax's module doc).
"""
import numpy as np
import pytest

from repro.api import (AZURE_NC96, DatasetProfile, SenecaConfig,
                       SenecaServer, SessionClosed, policy_names,
                       resolve_policy)

BACKENDS = ("numpy", "jax")


def _server(n=200, cache_bytes=None, split=(0.0, 0.0, 1.0), seed=3,
            **kw) -> SenecaServer:
    profile = DatasetProfile("synth", n, 1000, decoded_bytes=1000,
                             augmented_bytes=1000)
    return SenecaServer(SenecaConfig(
        cache_bytes=cache_bytes if cache_bytes is not None else 1000 * n,
        hardware=AZURE_NC96, dataset=profile, split=split, seed=seed, **kw))


# ----------------------------------------------------------------------
# session lifecycle
def test_open_session_hides_job_plumbing():
    server = _server()
    with server.open_session(batch_size=10) as sess:
        ids, forms = sess.next_batch_ids()
        assert ids.shape == (10,) and forms.shape == (10,)
        assert server.n_sessions == 1
        st = sess.stats()
        assert st["session"]["batch_size"] == 10
    assert server.n_sessions == 0


def test_closed_session_raises_clear_error():
    server = _server()
    sess = server.open_session(batch_size=8)
    sess.next_batch_ids()
    sess.close()
    with pytest.raises(SessionClosed, match="closed.*open_session"):
        sess.next_batch_ids()
    sess.close()                                   # idempotent
    # racing admissions from pipeline workers are dropped, not an error
    assert sess.admit(0, "augmented", b"v", 1000) is False


def test_server_close_closes_all_sessions():
    server = _server()
    sessions = [server.open_session(batch_size=4) for _ in range(3)]
    assert server.service.backend.n_jobs == 3
    server.close()
    assert server.n_sessions == 0
    for s in sessions:
        with pytest.raises(SessionClosed):
            s.next_batch_ids()


@pytest.mark.parametrize("backend", BACKENDS)
def test_session_churn_keeps_ods_metadata_consistent(backend):
    """Opening/closing sessions mid-run tracks the n_jobs refcount
    threshold and the per-job metadata footprint."""
    server = _server(backend=backend)
    eng = server.service
    s1 = server.open_session(batch_size=10)
    assert eng.backend.n_jobs == 1
    base_meta = eng.backend.metadata_bytes()

    # with one job, an augmented entry dies after a single serve — and as
    # the only cached entry it is guaranteed to be substituted into the
    # very first batch
    assert s1.admit(5, "augmented", b"v", 1000)
    ids, _ = s1.next_batch_ids()
    assert 5 in ids.tolist()
    assert eng.backend.status_of(np.array([5]))[0] == 0, \
        "threshold 1: first serve must refcount-evict"

    # second session raises the threshold to 2 mid-run; admit an entry no
    # job has seen yet so its refcount starts at 0
    s2 = server.open_session(batch_size=10)
    assert eng.backend.n_jobs == 2
    assert eng.backend.metadata_bytes() > base_meta
    fresh = next(i for i in range(200)
                 if i not in set(ids.tolist()) and i != 5)
    assert s2.admit(fresh, "augmented", b"v", 1000)
    for _ in range(200 // 10 - 1):           # s1 finishes its epoch alone
        s1.next_batch_ids()
    assert eng.backend.status_of(np.array([fresh]))[0] == 3, \
        "threshold 2: one job's serve must NOT evict"
    for _ in range(200 // 10):               # s2's epoch is the second use
        s2.next_batch_ids()
    assert eng.backend.status_of(np.array([fresh]))[0] == 0, \
        "threshold 2: the second job's serve completes the refcount"

    # closing s2 drops the threshold back; metadata shrinks
    s2.close()
    assert eng.backend.n_jobs == 1
    assert eng.backend.metadata_bytes() == base_meta
    s1.close()


# ----------------------------------------------------------------------
# policies
def test_policy_registry_names_and_errors():
    assert "ods" in policy_names("sampler")
    assert "naive" in policy_names("sampler")
    assert "unseen-only" in policy_names("admission")
    assert "capacity" in policy_names("admission")
    assert "refcount" in policy_names("eviction")
    assert "lru" in policy_names("eviction")
    with pytest.raises(ValueError, match="unknown sampler policy"):
        resolve_policy("sampler", "nope")
    with pytest.raises(ValueError, match="unknown policy kind"):
        from repro.api import register_policy
        register_policy("frobnicator", "x", object)


def test_naive_sampler_serves_exactly_requested():
    server = _server(use_ods=False)
    stats = server.stats()
    assert stats["policies"]["sampler"] == "naive"
    assert stats["policies"]["admission"] == "capacity"
    with server.open_session(batch_size=10) as sess:
        seen = []
        for _ in range(200 // 10):
            ids, _ = sess.next_batch_ids()
            seen.extend(ids.tolist())
        assert sorted(seen) == list(range(200))
    stats = server.stats()
    assert stats["substitutions"] == 0
    assert stats["hits"] + stats["misses"] == 200


def test_lru_eviction_baseline_churns_instead_of_rejecting():
    server = _server(cache_bytes=3 * 1000, eviction="lru",
                     sampler="naive", admission="capacity")
    eng = server.service
    assert eng.cache.parts["augmented"].policy == "lru"
    with server.open_session(batch_size=4):
        for sid in range(5):                     # capacity: 3 entries
            assert eng.admit(sid, "augmented", b"v", 1000)
        resident = eng.cache.parts["augmented"].keys()
        assert len(resident) == 3
        assert 0 not in resident and 4 in resident   # oldest evicted


def test_unseen_only_admission_rejects_all_seen_augmented():
    server = _server()
    eng = server.service
    with server.open_session(batch_size=10) as sess:
        ids, _ = sess.next_batch_ids()           # all misses -> all seen
        sid = int(ids[0])
        assert not eng.admit(sid, "augmented", b"v", 1000), \
            "augmented admission nobody can consume must be rejected"
        assert eng.admit(sid, "encoded", b"v", 1000) or \
            eng.tier_capacity("encoded") == 0    # other forms unaffected


# ----------------------------------------------------------------------
# backend parity (acceptance: same request stream, same invariants)
def _drive_epoch(server, n, B, n_cached):
    """Open two sessions, admit n_cached augmented entries, run exactly one
    epoch for each job, returning (per-job id lists, first batches)."""
    s1 = server.open_session(batch_size=B)
    s2 = server.open_session(batch_size=B)
    for sid in range(n_cached):
        assert s1.admit(sid, "augmented", b"v", 1000)
    first = {}
    seen = {0: [], 1: []}
    for step in range(n // B):
        for jid, sess in ((0, s1), (1, s2)):
            ids, forms = sess.next_batch_ids()
            if step == 0:
                first[jid] = forms
            seen[jid].extend(ids.tolist())
    s1.close()
    s2.close()
    return seen, first


@pytest.mark.parametrize("backend", BACKENDS)
def test_parity_invariants_per_backend(backend):
    n, B, n_cached = 96, 8, 48
    server = _server(n=n, backend=backend)
    seen, first = _drive_epoch(server, n, B, n_cached)

    # invariant 1: every sample exactly once per job per epoch
    for jid in (0, 1):
        assert sorted(seen[jid]) == list(range(n)), backend

    # invariant 2: cached-unseen preferred — with half the dataset cached
    # and batch << cached count, job 0's whole first batch is served from
    # cache in both backends (misses are substituted).  Job 1's first
    # batch can contain entries its own serve just refcount-evicted, so
    # only the first-served session gives a clean read.
    assert np.all(first[0] != 0), (backend, first[0])

    # invariant 3: refcount eviction at n_jobs — after one full epoch for
    # both jobs every admitted augmented entry has been consumed by both
    # and must be back to storage-resident
    status = server.service.backend.status_of(np.arange(n))
    assert int((status == 3).sum()) == 0, backend

    stats = server.stats()
    assert stats["hits"] > 0 and stats["substitutions"] > 0


def test_parity_numpy_vs_jax_same_stream_same_aggregates():
    """Same config, same seeds, same request stream: the two backends must
    agree on the invariant-level aggregates (coverage and full eviction),
    and their hit counts must land in the same regime."""
    n, B, n_cached = 96, 8, 48
    out = {}
    for backend in BACKENDS:
        server = _server(n=n, backend=backend)
        seen, _ = _drive_epoch(server, n, B, n_cached)
        st = server.stats()
        out[backend] = {
            "coverage": {j: sorted(seen[j]) for j in seen},
            "aug_left": int((server.service.backend.status_of(
                np.arange(n)) == 3).sum()),
            "hits": st["hits"], "total": st["hits"] + st["misses"],
        }
    a, b = out["numpy"], out["jax"]
    assert a["coverage"] == b["coverage"] == \
        {0: list(range(n)), 1: list(range(n))}
    assert a["aug_left"] == b["aug_left"] == 0
    assert a["total"] == b["total"] == 2 * n
    # every cached entry is served to each job exactly once before dying,
    # so both backends must count exactly n_cached hits per job
    assert a["hits"] == b["hits"] == 2 * n_cached


# ----------------------------------------------------------------------
# batch-granular admission (the device route's path): same
# policy decisions as N per-sample admits, one lock acquisition per batch
def test_admit_batch_matches_per_sample_admission():
    """Same entries, same policies, same capacity: admit_batch and a loop
    of admit() must leave identical residency, stats and ODS status."""
    entries = [(sid, b"v", 1000) for sid in range(5)]
    per, batch = _server(cache_bytes=3 * 1000), _server(cache_bytes=3 * 1000)
    s1 = per.open_session(batch_size=4)
    s2 = batch.open_session(batch_size=4)
    loop_ok = [s1.admit(sid, "augmented", v, nb) for sid, v, nb in entries]
    batch_ok = s2.admit_batch("augmented", entries)
    assert loop_ok == batch_ok.tolist() == [True] * 3 + [False] * 2
    assert per.service.cache.parts["augmented"].keys() == \
        batch.service.cache.parts["augmented"].keys()
    ids = np.arange(5)
    assert np.array_equal(per.service.backend.status_of(ids),
                          batch.service.backend.status_of(ids))
    s1.close()
    s2.close()


def test_admit_batch_unseen_only_rejects_all_seen():
    server = _server()
    with server.open_session(batch_size=10) as sess:
        ids, _ = sess.next_batch_ids()          # all misses -> all seen
        entries = [(int(s), b"v", 1000) for s in ids]
        assert not sess.admit_batch("augmented", entries).any(), \
            "augmented admissions nobody can consume must all be rejected"
        fresh = [(sid, b"v", 1000) for sid in range(200)
                 if sid not in set(ids.tolist())][:10]
        ok = sess.admit_batch("augmented", fresh)
        assert ok.all()
        marked = server.service.backend.status_of(
            np.asarray([sid for sid, _, _ in fresh]))
        assert (marked == 3).all(), "admitted batch must be ODS-marked"


def test_admit_batch_closed_session_drops_everything():
    server = _server()
    sess = server.open_session(batch_size=4)
    sess.close()
    ok = sess.admit_batch("augmented", [(0, b"v", 1000), (1, b"v", 1000)])
    assert ok.shape == (2,) and not ok.any()
    assert len(server.service.cache.parts["augmented"]) == 0


def test_admit_batch_zero_capacity_tier_fast_path():
    server = _server(split=(1.0, 0.0, 0.0))     # no augmented tier
    with server.open_session(batch_size=4) as sess:
        ok = sess.admit_batch("augmented", [(0, b"v", 1000)])
        assert not ok.any()
        assert sess.admit_batch("encoded", [(0, b"e", 1000)]).all()


# ----------------------------------------------------------------------
# legacy DSIPipeline shim (scheduled for removal, see repro.core.seneca):
# pin the positional-argument handling so dropping it in a later PR is a
# deliberate act, not a silent break
def test_legacy_dsipipeline_positional_batch_size():
    from repro.data.pipeline import DSIPipeline
    from repro.data.storage import RemoteStorage
    from repro.data.synthetic import tiny

    ds = tiny(n=64)
    server = _server(n=64, cache_bytes=64 * 4 * ds.augmented_bytes())
    storage = RemoteStorage(ds)
    # old positional form: DSIPipeline(job_id, service, storage, batch_size)
    with pytest.warns(DeprecationWarning, match="deprecated"):
        pipe = DSIPipeline(7, server.service, storage, 8)
    assert pipe.session.job_id == 7 and pipe.bs == 8
    batch = pipe.next_batch()
    assert batch["images"].shape[0] == 8
    pipe.stop()
    # keyword batch_size on the legacy form also still works
    with pytest.warns(DeprecationWarning):
        pipe2 = DSIPipeline(8, server.service, storage, batch_size=4)
    assert pipe2.bs == 4
    pipe2.stop()
    server.close()


def test_legacy_dsipipeline_bad_args_raise():
    from repro.data.pipeline import DSIPipeline
    from repro.data.storage import RemoteStorage
    from repro.data.synthetic import tiny

    ds = tiny(n=32)
    server = _server(n=32)
    # session-style call with a non-storage second arg
    with pytest.raises(TypeError, match="RemoteStorage"):
        DSIPipeline(server.open_session(batch_size=4), object())
    # legacy call missing batch_size entirely
    with pytest.warns(DeprecationWarning), \
            pytest.raises(TypeError, match="legacy"):
        DSIPipeline(1, server.service, RemoteStorage(ds))
    server.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_backend_selectable_from_server_kwarg(backend):
    profile = DatasetProfile("synth", 64, 1000, decoded_bytes=1000,
                             augmented_bytes=1000)
    cfg = SenecaConfig(cache_bytes=64000, hardware=AZURE_NC96,
                       dataset=profile, split=(0.0, 0.0, 1.0))
    server = SenecaServer(cfg, backend=backend)
    assert server.stats()["backend"] == backend
    with server.open_session(batch_size=8) as sess:
        ids, _ = sess.next_batch_ids()
        assert len(ids) == 8
