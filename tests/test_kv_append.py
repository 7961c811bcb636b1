"""The paged KV append (ops/kv_append.py): the kernel, interpreted, against
the XLA form and against a numpy loop over the rows, the pools compared bit
for bit; and which form the registry takes for the pools the kernel leaves
to XLA."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import ops
from deepspeed_tpu.ops import kv_append as kva
from deepspeed_tpu.ops import registry

S, MB, NB, L = 5, 6, 40, 3
# layout -> (kv-major, nkv, hd, bs): a standard page of two 16-token units,
# a kv-major page whose tokens fill the lanes
LAYOUTS = {"std": (False, 2, 128, 32), "kvmajor": (True, 2, 16, 128)}


def _steps(bs):
    """name -> (runs [(slot, first position, rows)], pad rows, rows a slot,
    layer).  Rows are packed run after run, pads behind them."""
    return {
        "chunk_starts_mid_page": ([(0, 5, bs + 3)], 2, 2 * bs, 1),
        "chunk_spans_J_pages": ([(1, bs - 3, 3 * bs + 1)], 0, 3 * bs + 1, 1),
        "run_exactly_fills_pages": ([(2, bs, 2 * bs)], 0, 2 * bs, 1),
        "one_row_slots_only": ([(0, 0, 1), (1, bs - 1, 1), (2, bs, 1),
                                (3, 3 * bs + 7, 1), (4, 17, 1)], 0, 1, 1),
        "a_slot_with_no_row": ([(0, 3, 9), (2, 2 * bs - 4, 6), (4, 0, 1)], 0,
                               12, 1),
        "pad_rows": ([(3, 1, 4)], 9, 8, 1),
        "only_pad_rows": ([], 6, 4, 1),
        "several_slots": ([(0, 7, bs), (1, 0, 1), (2, bs - 1, 2),
                           (3, 2 * bs + 5, bs + 9), (4, 4 * bs, 1)], 3,
                          bs + 9, 1),
        "base_of_a_later_layer": ([(0, 5, bs + 3), (4, 2, 1)], 1, 2 * bs, 2),
    }


def _case(rng, layout, step, dtype=np.float32, pools=2, nkv=None):
    km, nkv_, hd, bs = LAYOUTS[layout]
    nkv = nkv or nkv_
    runs, pads, per_slot, li = _steps(bs)[step]
    slot = np.concatenate([np.full(n, s) for s, _, n in runs]
                          + [np.full(pads, S)]).astype(np.int32)
    pos = np.concatenate([np.arange(p, p + n) for _, p, n in runs]
                         + [rng.integers(0, bs, pads)]).astype(np.int32)
    table = rng.permutation(NB)[:S * MB].reshape(S, MB).astype(np.int32)
    page = (nkv, hd, bs) if km else (nkv, bs, hd)
    old = [jnp.asarray(rng.standard_normal((L * NB,) + page), dtype)
           for _ in range(pools)]
    new = [jnp.asarray(rng.standard_normal((len(slot), nkv, hd)), dtype)
           for _ in range(pools)]
    plan = kva.append_plan(jnp.asarray(table), jnp.asarray(slot),
                           jnp.asarray(pos), bs, per_slot, km)
    want = [np.array(a.astype(jnp.float32)) for a in old]
    for x, pool in zip(new, want):
        x = np.asarray(x.astype(jnp.float32))
        for n in range(len(slot)):
            if slot[n] >= S:
                continue
            pg, off = li * NB + table[slot[n], pos[n] // bs], pos[n] % bs
            if km:
                pool[pg, :, :, off] = x[n]
            else:
                pool[pg, :, off, :] = x[n]
    return km, tuple(old), tuple(new), plan, li * NB, want


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g.astype(jnp.float32)), w)


@pytest.mark.parametrize("step", sorted(_steps(8)))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kernel_and_xla_form_write_the_same_bits(rng, layout, step):
    """Both forms against the loop over the rows: the same values in the
    same places, rows of a dropped slot never written, every other token of
    every pool as it was."""
    km, old, new, plan, base, want = _case(rng, layout, step)
    assert plan.unit.shape[0] <= S * ((_steps(LAYOUTS[layout][3])[step][2]
                                       + plan.granule - 2) // plan.granule + 1)
    _same(ops.paged_kv_append(old, new, plan, base, kv_major=km, impl="xla"),
          want)
    _same(ops.paged_kv_append(old, new, plan, jnp.int32(base), kv_major=km,
                              impl="pallas"), want)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_bfloat16_pools_come_back_bit_for_bit(rng, layout):
    """The kernel rotates the rows in float32: bfloat16 goes there and back
    unchanged."""
    km, old, new, plan, base, want = _case(rng, layout, "several_slots",
                                           dtype=jnp.bfloat16)
    _same(kva.pallas_paged_kv_append(old, new, plan, base, kv_major=km,
                                     interpret=True), want)
    _same(kva.xla_paged_kv_append(old, new, plan, base, kv_major=km), want)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_a_latent_pool_with_v_none(rng, layout, monkeypatch):
    """One pool of one head (latent pages, index keys): the kernel writes it
    like any other when asked to; left to the registry on a TPU it stays a
    row scatter, one update a row."""
    km, old, new, plan, base, want = _case(rng, layout, "several_slots",
                                           pools=1, nkv=1)
    _same(kva.pallas_paged_kv_append(old, new, plan, base, kv_major=km,
                                     interpret=True), want)
    assert not kva.supported(old, new, plan, base, kv_major=km)
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    registry.reset_dispatch_log()
    _same(ops.paged_kv_append(old, new, plan, base, kv_major=km), want)
    assert registry.dispatch_log() == [
        {"op": "paged_kv_append", "impl": "xla",
         "reason": "shape predicate refused", "count": 1}]


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_the_step_programs_shapes_take_the_kernel_on_a_tpu(rng, layout,
                                                           monkeypatch):
    """k and v of several heads, one row a slot or many: the registry's
    choice on a TPU is the kernel (interpreted here), by ``supported``
    alone."""
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    for step in ("one_row_slots_only", "several_slots"):
        km, old, new, plan, base, want = _case(rng, layout, step)
        registry.reset_dispatch_log()
        _same(ops.paged_kv_append(old, new, plan, base, kv_major=km), want)
        assert registry.dispatch_log() == [
            {"op": "paged_kv_append", "impl": "pallas", "reason": "auto",
             "count": 1}]


@pytest.mark.parametrize("tp", [1, 2], ids=["tp1", "tp2"])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8kv"])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_kv_write_on_a_tpu_takes_the_kernel_or_says_why_not(rng, layout,
                                                            quant, tp,
                                                            monkeypatch):
    """``model._kv_write`` with the registry told it is on a TPU: float
    pools go through the kernel, whole or per kv-head shard under the ``tp``
    shard_map, and come back as the XLA form leaves them; an int8 pool with
    its scale pools stays on the XLA form, and the dispatch log says so."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.inference.v2.model import _kv_writer
    from deepspeed_tpu.parallel import mesh as mesh_lib
    km, old, new, plan, base, want = _case(rng, layout, "several_slots",
                                           nkv=4)
    mesh = None if tp == 1 else mesh_lib.build_mesh(
        mesh_lib.MeshSpec(tp=tp, dp=1, fsdp=1))

    def place(a):
        if mesh is None:
            return a
        return jax.device_put(a, NamedSharding(mesh, P(
            None, "tp", *(None,) * (a.ndim - 2))))
    scales = (None, None)
    if quant:
        old = tuple(jnp.asarray(rng.integers(-9, 9, a.shape), jnp.int8)
                    for a in old)
        scales = tuple(jnp.asarray(rng.random(a.shape[:2] + (
            LAYOUTS[layout][3],)), jnp.float32) for a in old)
    args = tuple(place(a) for a in old + scales if a is not None)
    args = args + (None,) * (4 - len(args)) + new + (plan, base, km)
    want = _kv_writer(km, mesh)(*args[:-1])       # the XLA form: a CPU
    monkeypatch.setattr(registry, "_on_tpu", lambda: True)
    registry.reset_dispatch_log()
    got = _kv_writer(km, mesh)(*args[:-1])
    log, = registry.dispatch_log()
    assert (log["op"], log["impl"], log["reason"]) == (
        ("paged_kv_append", "xla", "shape predicate refused") if quant
        else ("paged_kv_append", "pallas", "auto"))
    for g, w in zip(got, want):
        assert (g is None) == (w is None)
        if w is not None:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if mesh is not None:
        assert got[0].sharding.spec[1] == "tp"


def test_the_op_is_in_the_report():
    assert "paged_kv_append" in ops.__all__
    registry.reset_dispatch_log()
    row, = [line for line in ops.op_report().splitlines()
            if line.startswith("paged_kv_append")]
    assert "pallas,xla" in row
