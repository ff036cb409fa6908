"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs ref.py oracles."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax.numpy as jnp
from repro.kernels import ref
from repro.kernels.alloc_score import (alloc_score_batch_pallas,
                                       alloc_score_pallas)
from repro.kernels.ebf_shadow import ebf_shadow_pallas

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- alloc
@pytest.mark.parametrize("n,r", [(1, 1), (7, 2), (128, 3), (1000, 4),
                                 (513, 2), (4096, 8)])
@pytest.mark.parametrize("dtype", [np.int32, np.int64])
def test_alloc_score_shapes(n, r, dtype):
    cap = RNG.integers(1, 16, (n, r)).astype(dtype)
    used = RNG.integers(0, 16, (n, r)).astype(dtype)
    avail = np.clip(cap - used, 0, None).astype(dtype)
    req = RNG.integers(0, 6, (r,)).astype(dtype)
    f1, s1 = alloc_score_pallas(jnp.asarray(avail), jnp.asarray(cap),
                                jnp.asarray(req), interpret=True)
    f2, s2 = ref.alloc_score_ref(jnp.asarray(avail), jnp.asarray(cap),
                                 jnp.asarray(req))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 300), r=st.integers(1, 5), seed=st.integers(0, 999))
def test_alloc_score_property(n, r, seed):
    rng = np.random.default_rng(seed)
    cap = rng.integers(1, 9, (n, r)).astype(np.int32)
    avail = rng.integers(0, 9, (n, r)).clip(0, cap).astype(np.int32)
    req = rng.integers(0, 5, (r,)).astype(np.int32)
    fit, score = alloc_score_pallas(jnp.asarray(avail), jnp.asarray(cap),
                                    jnp.asarray(req), interpret=True)
    fit = np.asarray(fit)
    # semantic: fit[i] == all(avail[i] >= req)
    expect = np.all(avail >= req[None, :], axis=1)
    np.testing.assert_array_equal(fit.astype(bool), expect)
    # scores within [0, r]
    assert np.all(np.asarray(score) >= -1e-6)
    assert np.all(np.asarray(score) <= r + 1e-6)


# ------------------------------------------------------------ alloc batch
@pytest.mark.parametrize("j,n,r", [(1, 1, 1), (3, 7, 2), (8, 128, 3),
                                   (17, 513, 2), (64, 1000, 4),
                                   (256, 64, 2)])
def test_alloc_score_batch_shapes(j, n, r):
    cap = RNG.integers(1, 16, (n, r)).astype(np.int32)
    avail = RNG.integers(0, 16, (n, r)).clip(0, cap).astype(np.int32)
    req = RNG.integers(0, 6, (j, r)).astype(np.int32)
    f1, s1 = alloc_score_batch_pallas(jnp.asarray(avail), jnp.asarray(cap),
                                      jnp.asarray(req), interpret=True)
    f2, s2 = ref.alloc_score_batch_ref(jnp.asarray(avail), jnp.asarray(cap),
                                       jnp.asarray(req))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-6)


def test_alloc_score_batch_rows_match_per_job_kernel():
    """Row j of the batched kernel == the per-job kernel on request j."""
    n, r, j = 257, 3, 19
    cap = RNG.integers(1, 12, (n, r)).astype(np.int32)
    avail = RNG.integers(0, 12, (n, r)).clip(0, cap).astype(np.int32)
    req = RNG.integers(0, 5, (j, r)).astype(np.int32)
    fb, sb = alloc_score_batch_pallas(jnp.asarray(avail), jnp.asarray(cap),
                                      jnp.asarray(req), interpret=True)
    for k in range(j):
        f1, s1 = alloc_score_pallas(jnp.asarray(avail), jnp.asarray(cap),
                                    jnp.asarray(req[k]), interpret=True)
        np.testing.assert_array_equal(np.asarray(fb)[k], np.asarray(f1))
        np.testing.assert_allclose(np.asarray(sb)[k], np.asarray(s1),
                                   atol=1e-6)


@settings(max_examples=15, deadline=None)
@given(j=st.integers(1, 40), n=st.integers(1, 200), r=st.integers(1, 5),
       seed=st.integers(0, 999))
def test_alloc_score_batch_property(j, n, r, seed):
    rng = np.random.default_rng(seed)
    cap = rng.integers(1, 9, (n, r)).astype(np.int32)
    avail = rng.integers(0, 9, (n, r)).clip(0, cap).astype(np.int32)
    req = rng.integers(0, 5, (j, r)).astype(np.int32)
    fit, score = alloc_score_batch_pallas(
        jnp.asarray(avail), jnp.asarray(cap), jnp.asarray(req),
        interpret=True)
    fit = np.asarray(fit)
    expect = np.all(avail[None, :, :] >= req[:, None, :], axis=2)
    np.testing.assert_array_equal(fit.astype(bool), expect)
    # the load score is a per-node quantity: identical across job rows
    score = np.asarray(score)
    np.testing.assert_allclose(score,
                               np.broadcast_to(score[0], score.shape),
                               atol=0)
    assert np.all(score >= -1e-6) and np.all(score <= r + 1e-6)


# ---------------------------------------------------------------- ebf
@pytest.mark.parametrize("m,n,r", [(1, 16, 1), (5, 100, 2), (33, 257, 3),
                                   (64, 1024, 4)])
def test_ebf_shadow_shapes(m, n, r):
    cap = RNG.integers(1, 8, (n, r)).astype(np.int32)
    avail = RNG.integers(0, 8, (n, r)).clip(0, cap).astype(np.int32)
    deltas = RNG.integers(0, 3, (m, n, r)).astype(np.int32)
    req = RNG.integers(0, 5, (r,)).astype(np.int32)
    f1 = ebf_shadow_pallas(jnp.asarray(avail), jnp.asarray(deltas),
                           jnp.asarray(req), interpret=True)
    f2 = ref.ebf_shadow_ref(jnp.asarray(avail), jnp.asarray(deltas),
                            jnp.asarray(req))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


def test_ebf_shadow_monotone():
    """Releases only free resources -> fit count is non-decreasing."""
    n, r, m = 64, 2, 10
    cap = np.full((n, r), 8, np.int32)
    avail = np.zeros((n, r), np.int32)
    deltas = RNG.integers(0, 2, (m, n, r)).astype(np.int32)
    req = np.array([3, 2], np.int32)
    fits = np.asarray(ebf_shadow_pallas(jnp.asarray(avail),
                                        jnp.asarray(deltas),
                                        jnp.asarray(req), interpret=True))
    assert np.all(np.diff(fits) >= 0)


@pytest.mark.parametrize("m", [1, 5, 8, 9, 33])
def test_ebf_shadow_fits_prefix_bucket_is_inert(m):
    """``ops.ebf_shadow_fits`` pads the prefix axis to a power of two with
    zero deltas; the padded call must give the unpadded fits."""
    from repro.kernels import ops

    n, r = 120, 2
    avail = RNG.integers(0, 4, (n, r)).astype(np.int32)
    deltas = RNG.integers(0, 2, (m, n, r)).astype(np.int32)
    req = np.array([3, 2], np.int32)
    got = ops.ebf_shadow_fits(avail, deltas, req)
    want = np.asarray(ebf_shadow_pallas(jnp.asarray(avail),
                                        jnp.asarray(deltas),
                                        jnp.asarray(req), interpret=True))
    assert got.shape == (m,)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("forced", ["interpret", "ref", "stub", None])
def test_kernel_mode_never_hides_the_tpu(monkeypatch, forced):
    """On a TPU backend the kernels run compiled; a forced interpreter or
    reference mode, or a value no mode has (``stub``), is refused, never
    silently obeyed."""
    import jax
    from repro.kernels import ops

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if forced is None:
        monkeypatch.delenv("REPRO_KERNELS", raising=False)
        assert ops.kernel_mode() == "tpu"
    else:
        monkeypatch.setenv("REPRO_KERNELS", forced)
        with pytest.raises(RuntimeError, match="REPRO_KERNELS"):
            ops.kernel_mode()


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 24), r=st.integers(1, 3), jobs=st.integers(0, 12),
       seed=st.integers(0, 999))
def test_shadow_walk_matches_host_scan(n, r, jobs, seed):
    """The compiled one-release-per-trip walk (fleet engine's EBF carry)
    must agree with the host prefix scan on random running-job sets —
    same shadow time, same availability at that instant, tie-grouping
    included (release times are drawn from a tiny range to force
    collisions)."""
    from repro.core.dispatchers.schedulers import EasyBackfilling
    from repro.kernels.ebf_shadow import INF_I, shadow_walk

    rng = np.random.default_rng(seed)
    cap = rng.integers(2, 8, (n, r)).astype(np.int32)
    avail = np.zeros((n, r), np.int32)
    k_cap = 3
    m = jobs + 2                                    # a couple of idle rows
    rel = np.full(m, INF_I, np.int32)
    assigned = np.full((m, k_cap), n, np.int32)     # trash id = n
    req = np.zeros((m, r), np.int32)
    releases = []
    for j in range(jobs):
        k = int(rng.integers(1, min(k_cap, n) + 1))
        nodes = rng.choice(n, size=k, replace=False)
        vec = rng.integers(0, 3, r).astype(np.int32)
        t = int(rng.integers(1, 5))                 # tight range -> ties
        rel[j] = t
        assigned[j, :k] = nodes
        req[j] = vec
        releases.append((t, nodes.astype(np.int64), vec.astype(np.int64)))
    releases.sort(key=lambda e: e[0])
    head_req = rng.integers(1, 4, r).astype(np.int32)
    need = int(rng.integers(1, 3))

    want_t, want_avail = EasyBackfilling._shadow(
        avail.copy(), head_req, need, releases)
    found, got_t, got_avail = shadow_walk(
        jnp.asarray(avail), jnp.asarray(rel), jnp.asarray(assigned),
        jnp.asarray(req), jnp.asarray(head_req), jnp.int32(need))
    if want_t is None:
        assert not bool(found)
    else:
        assert bool(found)
        assert int(got_t) == want_t
        np.testing.assert_array_equal(np.asarray(got_avail), want_avail)

