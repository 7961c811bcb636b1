"""``ops.ssm_pool_chunk_scan``: a mixed step's pass of prompt chunks through a
scan layer, in place in the packed state pool.  The kernel (forced, so
interpreted here, as ``tests/test_granite_hybrid.py`` forces the one-row
kernel) against the XLA form, at the two shapes the serving cells bring:
granite-4.0-h's (one group, two heads of 64 side by side on the lanes) and a
lightning layer's (a group a head, a head the lanes' width, ``dt`` = 1 under
a fixed decay, ``D`` = 0)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import ops
from deepspeed_tpu.ops.ssm_scan import (pack_state, packed_state_shape,
                                        pool_chunk_scan_supported)

Q, CHUNK, N_STATE, SLOTS, LAYERS, LAYER = 256, 128, 128, 6, 2, 1
SHAPES = {
    # heads, head width, groups, lanes of a pass, slots of the pass
    "granite": dict(h=4, p=64, g=1, slots=(4, 1, 3, 0)),
    "lightning": dict(h=2, p=128, g=2, slots=(5, 2)),
}
# the rows each lane holds (cut to the pass's lanes), and the lanes that live
COUNTS = {
    "full": ((Q, Q, Q, Q), (1, 1, 1, 1)),
    "partial": ((Q, 130, 77, 200), (1, 1, 1, 1)),    # 77: no second chunk
    "zero": ((0, Q, 131, 1), (1, 1, 1, 1)),
    "dead": ((190, 0, 0, 0), (1, 0, 0, 0)),
}


def operands(shape, seed, rows=Q):
    h, p, g = shape["h"], shape["p"], shape["g"]
    G = len(shape["slots"])
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    x, B, C = f(G, rows, h, p), f(G, rows, g, N_STATE), f(G, rows, g, N_STATE)
    if g > 1:       # lightning: dt = 1, a fixed decay a head, no D
        dt, A, D = jnp.ones((G, rows, h)), -jnp.asarray([0.02, 0.3]), \
            jnp.zeros((h,))
    else:
        dt, A, D = jax.nn.softplus(f(G, rows, h) - 1.0), -jnp.exp(f(h)), f(h)
    pool = f(LAYERS, SLOTS, *packed_state_shape(h, p, N_STATE))
    return (x, dt, A, B, C * 0.1, D), pool


def as_the_conv_leaves_them(x, dt, A, B, C, D):
    """The pass's operands as the op takes them: the rows ``[x | B | C]``."""
    G, rows = x.shape[:2]
    return (jnp.concatenate([a.reshape(G, rows, -1) for a in (x, B, C)], -1),
            dt, A, D)


@pytest.mark.parametrize("fresh", [True, False], ids=["fresh", "carried"])
@pytest.mark.parametrize("counts", sorted(COUNTS))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_xla_form_in_place(shape, counts, fresh):
    """``y`` on every live row and the pass's states equal the XLA form's to
    float32 rounding; every slot outside the pass, a dead lane's slot and
    the other layer come back bit for bit; a fresh slot starts from zero
    whatever the pool held (with no row, it is left zero)."""
    geo = SHAPES[shape]
    G = len(geo["slots"])
    front, pool = operands(geo, seed=len(shape) + len(counts))
    front = as_the_conv_leaves_them(*front)
    slots = jnp.asarray(geo["slots"], jnp.int32)
    count = jnp.asarray(COUNTS[counts][0][:G], jnp.int32)
    live = jnp.asarray(COUNTS[counts][1][:G], bool)
    back = (LAYER, slots, count, jnp.full((G,), fresh), live)
    assert pool_chunk_scan_supported(*front, pool, *back, chunk=CHUNK)
    want_y, want = ops.ssm_pool_chunk_scan(*front, pool, *back, chunk=CHUNK,
                                           impl="xla")
    got_y, got = jax.jit(lambda *a: ops.ssm_pool_chunk_scan(
        *a, chunk=CHUNK, impl="pallas"))(*front, pool, *back)
    for i in range(G):
        if live[i]:
            n = int(count[i])
            np.testing.assert_allclose(got_y[i, :n], want_y[i, :n],
                                       rtol=2e-5, atol=5e-5)
    assert got_y.shape == front[0].shape[:2] + (geo["h"] * geo["p"],)
    # (values of some tens: the sums of ``dt A`` are taken in another order)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=5e-5)
    touched = [int(s) for s, alive in zip(slots, live) if alive]
    others = [s for s in range(SLOTS) if s not in touched]
    np.testing.assert_array_equal(got[LAYER, others], pool[LAYER, others])
    np.testing.assert_array_equal(got[1 - LAYER], pool[1 - LAYER])
    assert not np.array_equal(got[LAYER, touched[-1]],
                              pool[LAYER, touched[-1]])
    if fresh and counts == "zero":
        assert not np.asarray(got[LAYER, int(slots[0])]).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_prompt_fed_as_three_chunks_ends_where_one_scan_does(shape, dtype):
    """One prompt of 2 Q + 100 rows through three passes (fresh, then
    carried twice, the last chunk partial) leaves in its slot the state a
    single dense scan of all its rows leaves, and gives the same ``y``.
    With the conv's rows in bfloat16, as a serving model has them, the
    kernel's products with a state take B and C as they are and the other
    operand in its three bfloat16 parts, which is all ``HIGHEST`` computes
    of them: the same state to float32 rounding."""
    geo = dict(SHAPES[shape], slots=SHAPES[shape]["slots"][:1])
    (x, dt, A, B, C, D), pool = operands(geo, seed=7, rows=3 * Q)
    x, B, C = (a.astype(dtype) for a in (x, B, C))
    h, p = geo["h"], geo["p"]
    rows = 2 * Q + 100
    zero = jnp.zeros((1, h, p, N_STATE))
    want_y, want = ops.ssm_chunk_scan(
        x[:, :rows], dt[:, :rows], A, B[:, :rows], C[:, :rows], D, zero,
        chunk=CHUNK)
    slots = jnp.asarray(geo["slots"], jnp.int32)
    step = jax.jit(lambda pool, fresh, count, *a: ops.ssm_pool_chunk_scan(
        *a, pool, LAYER, slots, count, fresh, jnp.ones((1,), bool),
        chunk=CHUNK, impl="pallas"))
    got, ys = pool, []
    for i, n in enumerate((Q, Q, 100)):
        at = slice(i * Q, (i + 1) * Q)
        y, got = step(got, jnp.asarray([i == 0]), jnp.asarray([n], jnp.int32),
                      *as_the_conv_leaves_them(x[:, at], dt[:, at], A,
                                               B[:, at], C[:, at], D))
        ys.append(y[:, :n])
    np.testing.assert_allclose(jnp.concatenate(ys, 1),
                               want_y.reshape(1, rows, h * p), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(got[LAYER, int(slots[0])],
                               pack_state(want)[0], rtol=1e-4, atol=1e-4)
