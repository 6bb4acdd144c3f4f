"""Power retention (engine/models/retention.py, engine/pallas/retention.py)
against the quadratic form written out in numpy float64:

    a_tj = exp(sum_{l=j+1..t} log g_l) (q_t . k_j)^2,   y_t = a v / sum a

for the feature map, the one-token recurrence, the chunked runs (chunk
and page boundaries, a run that restarts from its slot row, a capture
at a boundary, the scheduler's flat buffer with an inert sequence) and
the Pallas step in interpret mode. Group 5 over 2 kv heads, so a kv
head's queries are not a power of two."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from theroundtaible_tpu.engine.models import retention as R  # noqa: E402
from theroundtaible_tpu.engine.pallas import retention as kernel  # noqa: E402

K, GROUP, D = 2, 5, 16
TOL = 2e-4


def inputs(seed, t, d=D, k=K, group=GROUP):
    rng = np.random.RandomState(seed)
    q = rng.randn(t, k * group, d).astype(np.float32) * d ** -0.25
    kk = rng.randn(t, k, d).astype(np.float32) * d ** -0.25
    v = rng.randn(t, k, d).astype(np.float32)
    log_g = np.log(rng.uniform(0.9, 0.999, size=(t, k))).astype(np.float32)
    return q, kk, v, log_g


def quadratic(q, k, v, log_g):
    """-> y [T, H, D] in float64, no state, no feature map."""
    t, h, d = q.shape
    group = h // k.shape[1]
    b = np.cumsum(log_g.astype(np.float64), axis=0)
    y = np.zeros((t, h, d))
    for n in range(h):
        m = n // group
        s = q[:, n].astype(np.float64) @ k[:, m].astype(np.float64).T
        a = np.tril(np.exp(b[:, m][:, None] - b[:, m][None, :]) * s ** 2)
        y[:, n] = a @ v[:, m].astype(np.float64) / a.sum(-1, keepdims=True)
    return y


def empty(rows, d=D, k=K):
    nd = R.feature_rows(d)
    return (jnp.zeros((rows, k, nd, d, d), jnp.float32),
            jnp.zeros((rows, k, nd, d), jnp.float32))


@pytest.mark.parametrize("d", [2, 16, 128])
def test_the_feature_map_squares_the_dot_product(d):
    rng = np.random.RandomState(d)
    q, k = rng.randn(7, d), rng.randn(7, d)
    got = np.sum(np.asarray(R.phi(jnp.asarray(q))) *
                 np.asarray(R.phi(jnp.asarray(k))), axis=(-1, -2))
    assert np.allclose(got, (q * k).sum(-1) ** 2, rtol=1e-4, atol=1e-5)
    assert R.phi(jnp.zeros((d,))).shape == (R.feature_rows(d), d)
    assert R.state_rows(d) >= R.state_rows_min(d)
    assert R.state_rows(128) == 8320 and R.state_rows_min(128) == 8256


def test_the_recurrence_a_token_at_a_time():
    q, k, v, log_g = inputs(1, 40)
    want = quadratic(q, k, v, log_g)
    ret, retn = empty(1)
    for t in range(40):
        y, ret, retn = R.step_rows(
            jnp.asarray(q[t]).reshape(1, K, GROUP, D), jnp.asarray(k[t])[None],
            jnp.asarray(v[t])[None], jnp.asarray(log_g[t])[None], ret, retn)
        assert np.abs(np.asarray(y).reshape(-1, D) - want[t]).max() < TOL


def runs_of(row0, pos0, length, slot, cap_n=None, snap_idx=None):
    n = len(row0)
    zero = [0] * n
    return {key: jnp.asarray(val, jnp.int32) for key, val in (
        ("row0", row0), ("pos0", pos0), ("len", length), ("slot", slot),
        ("cap_n", cap_n or zero), ("snap_idx", snap_idx or zero))}


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunked_runs_cross_chunk_boundaries(chunk):
    q, k, v, log_g = inputs(2, 50)
    want = quadratic(q, k, v, log_g)
    ret, retn = empty(3)
    y, ret, retn, _ = R._retention_runs(
        *map(jnp.asarray, (q, k, v, log_g)),
        runs_of([0], [0], [50], [1]), ret, retn, None, chunk)
    assert np.abs(np.asarray(y) - want).max() < TOL
    assert not np.asarray(ret[0]).any() and np.asarray(ret[1]).any()


def test_a_run_restarts_from_its_slot_row_and_leaves_a_capture():
    """Tokens 0..36 in one dispatch, 37..69 in the next, from position
    37 (inside a page of 16): the second run's first chunk ends at the
    page boundary 48. The state captured at 64 continues a third slot
    exactly."""
    q, k, v, log_g = inputs(3, 90)
    want = quadratic(q, k, v, log_g)
    ret, retn = empty(4)
    snaps = empty(3)
    y1, ret, retn, snaps = R._retention_runs(
        *map(jnp.asarray, (q[:37], k[:37], v[:37], log_g[:37])),
        runs_of([0], [0], [37], [2]), ret, retn, snaps, 16)
    y2, ret, retn, snaps = R._retention_runs(
        *map(jnp.asarray, (q[37:70], k[37:70], v[37:70], log_g[37:70])),
        runs_of([0], [37], [33], [2], [27], [1]), ret, retn, snaps, 16)
    got = np.concatenate([np.asarray(y1), np.asarray(y2)])
    assert np.abs(got - want[:70]).max() < TOL
    # the capture is the state after 64 tokens: restore it elsewhere
    ret = ret.at[0].set(snaps[0][1])
    retn = retn.at[0].set(snaps[1][1])
    y3, ret, retn, _ = R._retention_runs(
        *map(jnp.asarray, (q[64:], k[64:], v[64:], log_g[64:])),
        runs_of([0], [64], [26], [0]), ret, retn, None, 16)
    assert np.abs(np.asarray(y3) - want[64:]).max() < TOL


def test_the_flat_buffer_two_sequences_and_an_inert_one():
    """Two runs aligned to 8 rows in one buffer (a join of 21 tokens
    from position 0 and one decode token of a sequence at position 30),
    and the inert sequence every pad points at (len 1 at row 0, the
    scratch slot): it has no run."""
    qa, ka, va, ga = inputs(4, 21)
    qb, kb, vb, gb = inputs(5, 31)
    ret, retn = empty(4)                      # rows 0..2, scratch 3
    _y, ret, retn, _ = R._retention_runs(
        *map(jnp.asarray, (qb[:30], kb[:30], vb[:30], gb[:30])),
        runs_of([0], [0], [30], [1]), ret, retn, None, 16)
    buf = [np.zeros((32,) + a.shape[1:], np.float32)
           for a in (qa, ka, va, ga)]
    for dst, a, b in zip(buf, (qa, ka, va, ga), (qb, kb, vb, gb)):
        dst[:21], dst[24] = a, b[30]
    rg = {"seq_start": jnp.asarray([0, 24, 0]),
          "seq_pos0": jnp.asarray([0, 30, 0]),
          "seq_len": jnp.asarray([21, 1, 1]),
          "seq_slot": jnp.asarray([0, 1, 3]), "cap_n": jnp.zeros(3, int)}
    real = np.asarray(rg["seq_slot"]) < 3
    runs = runs_of([0, 24, 0], [0, 30, 0], np.where(real, [21, 1, 1], 0),
                   [0, 1, 3])
    y, ret, retn, _ = R._retention_runs(*map(jnp.asarray, buf), runs, ret,
                                        retn, None, 16)
    assert np.abs(np.asarray(y[:21]) - quadratic(qa, ka, va, ga)).max() < TOL
    assert np.abs(np.asarray(y[24])
                  - quadratic(qb, kb, vb, gb)[30]).max() < TOL
    assert not np.asarray(ret[3]).any()       # the scratch row: untouched


def test_a_row_that_must_not_advance_keeps_its_state():
    q, k, v, log_g = inputs(6, 2)
    ret, retn = empty(1)
    _, ret, retn = R.step_rows(
        jnp.asarray(q[0]).reshape(1, K, GROUP, D), jnp.asarray(k[0])[None],
        jnp.asarray(v[0])[None], jnp.asarray(log_g[0])[None], ret, retn)
    _, ret2, retn2 = R.step_rows(
        jnp.asarray(q[1]).reshape(1, K, GROUP, D),
        jnp.zeros((1, K, D)), jnp.asarray(v[1])[None],
        jnp.zeros((1, K)), ret, retn)
    assert np.array_equal(np.asarray(ret2), np.asarray(ret))
    assert np.array_equal(np.asarray(retn2), np.asarray(retn))


def test_the_pallas_step_in_interpret_mode_is_the_recurrence():
    """The kernel at its own geometry (a lane row a head, group 5), on
    scattered state rows, with a pad row on the scratch row; the arrays
    it was not given stay as they were."""
    d, k, rows = 128, 2, 4
    assert kernel.decline_reason(d, GROUP).startswith("not on a TPU")
    rng = np.random.RandomState(7)
    nd = R.feature_rows(d)
    ret = jnp.asarray(rng.randn(rows, k, nd, d, d).astype(np.float32))
    retn = jnp.asarray(np.abs(rng.randn(rows, k, nd, d)).astype(np.float32))
    q, kk, v, log_g = inputs(8, 3, d=d, k=k)
    q = jnp.asarray(q).reshape(3, k, GROUP, d)
    at = jnp.asarray([2, 0, 3], jnp.int32)
    want_y, want_ret, want_retn = R.step_rows(
        q, jnp.asarray(kk), jnp.asarray(v), jnp.asarray(log_g), ret[at],
        retn[at])
    y, new, newn = kernel.retention_step(
        q, jnp.asarray(kk), jnp.asarray(v), jnp.asarray(log_g), ret, retn,
        at, interpret=True)
    assert np.allclose(np.asarray(y), np.asarray(want_y), rtol=2e-4,
                       atol=2e-4)
    assert np.allclose(np.asarray(new[at]), np.asarray(want_ret),
                       rtol=1e-5, atol=1e-5)
    assert np.allclose(np.asarray(newn[at]), np.asarray(want_retn),
                       rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(new[1]), np.asarray(ret[1]))


@pytest.mark.parametrize("head_dim,group,why", [
    (64, 5, "head_dim 64"), (128, 16, "group 16")])
def test_the_kernel_declines_what_it_was_not_written_for(monkeypatch,
                                                         head_dim, group,
                                                         why):
    monkeypatch.setattr(kernel, "_interpret", lambda: False)
    assert why in kernel.decline_reason(head_dim, group)
    assert kernel.decline_reason(128, 5) is None


def test_one_token_runs_go_through_the_step_kernel_and_only_they():
    """A flat buffer with two decode rows (sequences 1 and 3) among a
    join and an inert sequence: the kernel visits the two, in place, and
    leaves every other state row as it was."""
    d, k, group, rows = 128, 2, GROUP, 5
    rng = np.random.RandomState(11)
    nd = R.feature_rows(d)
    ret = jnp.asarray(rng.randn(rows, k, nd, d, d).astype(np.float32))
    retn = jnp.asarray(np.abs(rng.randn(rows, k, nd, d)).astype(np.float32))
    q, kk, v, log_g = inputs(12, 24, d=d, k=k, group=group)
    row0 = jnp.asarray([0, 8, 0, 16], jnp.int32)
    single = jnp.asarray([False, True, False, True])
    slot = jnp.asarray([0, 3, 4, 1], jnp.int32)
    y, order, new, newn = R.single_token_runs(
        *map(jnp.asarray, (q, kk, v, log_g)), row0, single, slot, ret, retn,
        interpret=True)
    assert list(np.asarray(order)[:2]) == [1, 3]
    at = jnp.asarray([8, 16])
    want_y, want_ret, _ = R.step_rows(
        jnp.asarray(q)[at].reshape(2, k, group, d), jnp.asarray(kk)[at],
        jnp.asarray(v)[at], jnp.asarray(log_g)[at], ret[slot[single]],
        retn[slot[single]])
    assert np.allclose(np.asarray(y[:2]).reshape(2, k, group, d),
                       np.asarray(want_y), rtol=2e-4, atol=2e-4)
    assert np.allclose(np.asarray(new[jnp.asarray([3, 1])]),
                       np.asarray(want_ret), rtol=1e-5, atol=1e-5)
    for untouched in (0, 2, 4):
        assert np.array_equal(np.asarray(new[untouched]),
                              np.asarray(ret[untouched]))
