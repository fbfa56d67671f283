"""Multi-worker map/combine/reduce correctness (repro.cluster).

The acceptance bar is MERGE PARITY: for any worker count and any merge
arrival order, the coordinator's output is bit-identical to the
single-process ``randomized_cca_streaming`` on the same store — the
merge is a sum of disjoint-row statistics reduced through a fixed
pairwise tree, so not even the last ulp may move."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.rcca import (
    MERGE_GROUP_CHUNKS,
    PairwiseStack,
    RCCAConfig,
    SegmentedAccumulator,
    init_Q,
    jit_update_fn,
    merge_final_stats,
    merge_power_stats,
    randomized_cca_streaming,
    reduce_group_partials,
    stats_init_fn,
)
from repro.cluster import ClusterCoordinator, run_worker
from repro.cluster import partials as pt
from repro.cluster.worker import WorkerKilled
from repro.data import PlantedCCAData
from repro.store import ingest_planted

N, DA, DB, CHUNK = 1536, 28, 20, 128  # 12 chunks
G = 2  # merge group: 6 groups → interesting splits at 1/2/4 workers
CFG = RCCAConfig(k=4, p=8, q=1, nu=0.01, center=True)
KEY = 5


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    data = PlantedCCAData(n=N, da=DA, db=DB, rank=5, noise=0.4,
                          seed=11, chunk=CHUNK)
    return ingest_planted(str(tmp_path_factory.mktemp("cluster") / "store"),
                          data)


@pytest.fixture(scope="module")
def streaming_ref(store):
    """Single-process reference per engine, on the exact store bytes."""
    A, B = store.materialize()
    Ac = jnp.asarray(A).reshape(store.n_chunks, CHUNK, DA)
    Bc = jnp.asarray(B).reshape(store.n_chunks, CHUNK, DB)
    cache = {}

    def get(engine):
        if engine not in cache:
            cache[engine] = randomized_cca_streaming(
                Ac, Bc, CFG, jax.random.PRNGKey(KEY), engine=engine,
                merge_group=G)
        return cache[engine]

    return get


def assert_bit_identical(r1, r2):
    for name in ("Xa", "Xb", "rho", "Qa", "Qb"):
        a1, a2 = np.asarray(getattr(r1, name)), np.asarray(getattr(r2, name))
        assert np.array_equal(a1, a2), f"{name} differs"


# -- mergeable statistics --------------------------------------------------


def _chunk_stats(store, kind, idxs, Qa, Qb, engine="jnp"):
    upd = jit_update_fn(kind, engine)
    s = stats_init_fn(kind, store.da, store.db, CFG.sketch)()
    for i in idxs:
        a, b = store.get_chunk(i)
        s = upd(s, jnp.asarray(a), jnp.asarray(b), Qa, Qb)
    return s


@pytest.mark.parametrize("kind,merge", [("power", merge_power_stats),
                                        ("final", merge_final_stats)])
def test_merge_stats_is_exact_combiner(store, kind, merge):
    """stats(S₁ ∪ S₂) == stats(S₁) ⊕ stats(S₂) when the sets split on
    the accumulation boundary — the map/reduce combiner law."""
    Qa, Qb = init_Q(jax.random.PRNGKey(KEY), DA, DB, CFG)
    s_all = _chunk_stats(store, kind, [0, 1, 2, 3], Qa, Qb)
    s_left = _chunk_stats(store, kind, [0, 1], Qa, Qb)
    s_right = _chunk_stats(store, kind, [2, 3], Qa, Qb)
    merged = merge(s_left, s_right)
    for f, x, y in zip(s_all._fields, s_all, merged):
        # exact as algebra; fp reassociation moves near-zero entries,
        # hence the absolute term
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-5,
                                   atol=1e-2, err_msg=f)
    assert float(merged.n) == 4 * CHUNK


def test_pairwise_tree_reduce_is_order_independent(store):
    """reduce_group_partials gives the bitwise single-process result no
    matter what order the partials dict was populated in (completion
    order must not matter)."""
    Qa, Qb = init_Q(jax.random.PRNGKey(KEY), DA, DB, CFG)
    upd = jit_update_fn("power", "jnp")
    init = stats_init_fn("power", DA, DB, CFG.sketch)
    nc = store.n_chunks
    partials = {}
    for g in range(-(-nc // G)):
        partials[g] = _chunk_stats(store, "power",
                                   range(g * G, min(nc, (g + 1) * G)), Qa, Qb)
    acc = SegmentedAccumulator(init, nc, G)
    for i in range(nc):
        a, b = store.get_chunk(i)
        acc.update(i, upd, jnp.asarray(a), jnp.asarray(b), Qa, Qb)
    single = acc.result()
    for order in (sorted(partials), sorted(partials, reverse=True),
                  [3, 0, 5, 1, 4, 2]):
        merged = reduce_group_partials({g: partials[g] for g in order},
                                       init, nc, G)
        for x, y in zip(single, merged):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def test_reduce_rejects_missing_group(store):
    init = stats_init_fn("power", DA, DB, CFG.sketch)
    with pytest.raises(ValueError, match="missing"):
        reduce_group_partials({0: init()}, init, store.n_chunks, G)


def test_pairwise_stack_depth_matches_popcount():
    init = stats_init_fn("power", 4, 3, 2)
    for m in (0, 1, 2, 3, 7, 8, 12, 37):
        st = PairwiseStack()
        for _ in range(m):
            st.push(init())
        assert len(st.stack) == PairwiseStack.depth_after(m) == bin(m).count("1")


# -- coordinator merge parity (the acceptance criterion) -------------------


@pytest.mark.parametrize("engine", ["jnp", "kernels"])
@pytest.mark.parametrize("workers", [1, 2, 4])
def test_coordinator_bit_identical_to_streaming(store, streaming_ref,
                                                tmp_path, engine, workers):
    co = ClusterCoordinator(store, CFG, str(tmp_path / "cl"),
                            n_workers=workers, engine=engine, merge_group=G)
    res = co.fit(jax.random.PRNGKey(KEY))
    assert_bit_identical(streaming_ref(engine), res)
    cl = res.diagnostics["cluster"]
    assert cl["n_workers"] == workers and cl["n_groups"] == 6
    assert all(p["redispatched_groups"] == [] for p in cl["passes"])


def test_coordinator_default_merge_group_matches_core(store, tmp_path):
    """Left to defaults, coordinator and streaming share
    MERGE_GROUP_CHUNKS — the bit-parity contract holds out of the box."""
    co = ClusterCoordinator(store, CFG, str(tmp_path / "cl"), n_workers=2,
                            engine="jnp")
    assert co.merge_group == MERGE_GROUP_CHUNKS


# -- worker unit behavior --------------------------------------------------


def _publish_round(store, cluster_dir, pass_idx=0, kind="power",
                   engine="jnp", fit_id="fitX"):
    from repro.cluster.coordinator import algo_meta

    Qa, Qb = init_Q(jax.random.PRNGKey(KEY), store.da, store.db, CFG)
    expect = pt.binding_meta(fit_id=fit_id, pass_idx=pass_idx, kind=kind,
                             engine=engine, fingerprint=store.fingerprint(),
                             merge_group=G, algo=algo_meta(CFG))
    pt.write_round(cluster_dir, pass_idx, Qa, Qb, {**expect, "n_shards": 2})
    return expect


def test_coordinator_refuses_tpu_host(store, tmp_path, monkeypatch):
    """One process per chip: a coordinator on a TPU host would hold the
    chip its workers need, so it refuses at once instead of hanging at
    the barrier."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="one process at a time"):
        ClusterCoordinator(store, CFG, str(tmp_path / "c"), n_workers=2)
    assert not (tmp_path / "c").exists()


def test_worker_killed_mid_shard_resumes_from_cursor(store, tmp_path):
    """A killed worker re-run with the same shard id picks up mid-shard:
    published groups are skipped, the in-flight group resumes from the
    cursor, and the partial set ends up identical to an unkilled run."""
    cd_kill = str(tmp_path / "kill")
    cd_ref = str(tmp_path / "ref")
    expect = _publish_round(store, cd_kill)
    _publish_round(store, cd_ref)

    # worker 0 of 2 with G=2 owns groups 0,2,4 → chunks 0,1,4,5,8,9;
    # kill after global chunk 5 (mid-shard, cursor at every chunk)
    with pytest.raises(WorkerKilled):
        run_worker(store.path, cd_kill, 0, 2, 0, ckpt_every=1, prefetch=0,
                   kill_at_chunk=5)
    have = pt.collect_partials(cd_kill, 0, 6, expect)
    assert set(have) == {0, 2}  # groups before the kill are published

    resumed = run_worker(store.path, cd_kill, 0, 2, 0, prefetch=0)
    assert resumed == 1  # only group 4 was left
    run_worker(store.path, cd_ref, 0, 2, 0, prefetch=0)
    for g in (0, 2, 4):
        s1, m1 = pt.read_partial(cd_kill, 0, g)
        s2, _ = pt.read_partial(cd_ref, 0, g)
        for x, y in zip(s1, s2):
            assert np.array_equal(np.asarray(x), np.asarray(y)), g


def test_worker_is_idempotent_after_completion(store, tmp_path):
    """Re-running a finished shard publishes nothing new (at-most-once:
    valid partials are recognized and skipped)."""
    cd = str(tmp_path / "idem")
    _publish_round(store, cd)
    assert run_worker(store.path, cd, 0, 2, 0, prefetch=0) == 3
    assert run_worker(store.path, cd, 0, 2, 0, prefetch=0) == 0


def test_worker_rejects_foreign_store(store, tmp_path):
    """A round published against different data must not fold: the
    fingerprint guard fires before any chunk is read."""
    cd = str(tmp_path / "foreign")
    other = ingest_planted(
        str(tmp_path / "other_store"),
        PlantedCCAData(n=N, da=DA, db=DB, rank=5, seed=99, chunk=CHUNK))
    _publish_round(other, cd)
    with pytest.raises(ValueError, match="different\\s+store"):
        run_worker(store.path, cd, 0, 2, 0, prefetch=0)
