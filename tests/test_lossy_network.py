"""The lossy network on the device lane: ``MeshPhaseKernel.lossy_window``
and ``MeshEngine(net_loss=...)`` held to ``chipbench/reference/kvnet_plain.py``
(which imports nothing of the program).

- the kernel decides every slot as the reference does (nulls before each op,
  slots consumed, slots by phases) at n = 3, 5 and 7, with and without
  crashed replicas, slots past four phases among them;
- the program's numpy twin of the draws (proposals, views) against the
  reference's;
- the lossless path is as it was: the mixed program of a kernel without
  loss has the operands it had and none of the network's draws, and an
  engine without loss dispatches it;
- the engine refuses what it cannot run under loss and raises where it
  would demote; ``sync_to_host`` keeps the lane.

CPU only (the conftest's eight virtual devices).
"""

from __future__ import annotations

import numpy as np
import pytest

from chipbench.reference.kvnet_plain import Network
from rabia_tpu.apps.vector_kv import VectorShardedKV
from rabia_tpu.core.errors import RabiaError, ValidationError
from rabia_tpu.parallel import MeshEngine, make_mesh
from rabia_tpu.parallel import mesh as mesh_mod
from rabia_tpu.parallel.mesh import MeshPhaseKernel

S, W, SEED = 64, 16, 77


def _reference(R, loss, dead, slots, need):
    """Per shard the reference's nulls before each op, slots consumed and
    consumed slots by phases (bins 1..64)."""
    alive = ~np.isin(np.arange(R), dead)
    net = Network(R, SEED, loss, alive=alive)
    nulls = np.zeros((W, S), np.int64)
    used = np.zeros(S, np.int64)
    hist = np.zeros(64, np.int64)
    for s in range(S):
        value, phases = net.decide(np.full(400, s), slots[s] + np.arange(400))
        ones = np.nonzero(value == 1)[0][: need[s]]
        assert len(ones) == need[s]
        used[s] = ones[-1] + 1 if need[s] else 0
        nulls[: need[s], s] = np.diff(np.concatenate([[-1], ones])) - 1
        hist += np.bincount(phases[: used[s]], minlength=65)[1:65]
    return nulls, used, hist


def _kernel_window(R, loss, dead, slots=None):
    import jax

    k = MeshPhaseKernel(S, R, make_mesh(), seed=SEED, net_loss=loss)
    alive = np.ones((S, R), bool)
    alive[:, list(dead)] = False
    if slots is None:
        slots = (np.arange(S, dtype=np.int32) * 37) % 211
    need = np.full(S, W, np.int32)
    need[5], need[6] = 0, 3  # a shard with no op, one with a few
    got = jax.jit(lambda a, s, n: k.lossy_window(a, s, n, W=W))(
        k.place(alive), slots, need
    )
    return slots, need, tuple(map(np.asarray, got))


CASES = [
    (3, 0.2, ()),
    (3, 0.2, (2,)),
    (5, 0.05, ()),
    (5, 0.3, (1,)),
    (7, 0.1, ()),
    (7, 0.1, (1, 4)),
]


@pytest.mark.parametrize("R, loss, dead", CASES)
def test_lossy_kernel_matches_the_reference(R, loss, dead):
    slots, need, (nulls, used, ok, iters, hist) = _kernel_window(R, loss, dead)
    want_nulls, want_used, want_hist = _reference(R, loss, dead, slots, need)
    assert bool(ok)
    assert (used == want_used).all()
    assert (nulls.astype(np.int64) == want_nulls).all()
    assert (hist == want_hist).all()
    assert hist.sum() == used.sum()  # every consumed slot counted once
    assert nulls.sum() > 0
    if R - len(dead) > R // 2 + 1:  # views leave someone out: second phases
        assert hist[1:].sum() > 0
    assert iters >= np.nonzero(hist)[0].max() + 1  # the loop ran the longest


def test_lossy_kernel_decides_slots_past_four_phases():
    # every shard's window starts at a slot the reference needs more than
    # four phases for, where one exists among its first 4,000: a fixed
    # four-phase scan would leave those slots undecided
    _, phases = Network(5, SEED, 0.05).decide(np.repeat(np.arange(S), 4000),
                                              np.tile(np.arange(4000), S))
    long = phases.reshape(S, 4000) > 4
    slots = np.where(long.any(1), long.argmax(1), 0).astype(np.int32)
    assert long.any(1).sum() >= S // 2
    slots, need, (nulls, used, ok, iters, hist) = _kernel_window(5, 0.05, (), slots)
    want_nulls, want_used, want_hist = _reference(5, 0.05, (), slots, need)
    assert bool(ok) and (hist == want_hist).all() and (used == want_used).all()
    assert (nulls.astype(np.int64) == want_nulls).all()
    assert hist[4:].sum() >= long.any(1).sum() - 2  # shards 5, 6 may not hold theirs
    assert iters > 4


def test_numpy_twin_of_the_draws_is_the_reference_model():
    net = Network(5, SEED, 0.3, alive=[True, False, True, True, True])
    shards = np.repeat(np.arange(8), 50).astype(np.uint32)
    slots = np.tile(np.arange(50), 8).astype(np.uint32)
    base = mesh_mod._net_base(SEED, shards, slots, xp=np)
    from chipbench.reference import kvnet_plain

    want = kvnet_plain.chain(kvnet_plain.chain(
        kvnet_plain.mix32(np.full(len(shards), SEED, np.uint32) ^ np.uint32(kvnet_plain.NET)),
        shards), slots)
    assert (base == want).all()
    prop = mesh_mod._net_proposals(base, 5, 0.3, xp=np)  # [R, n]
    assert (prop.T.astype(np.int8) == net._proposals(base)).all()
    alive = net.alive.reshape(5, 1)
    for e in (0, 1, 2, 7):
        views = mesh_mod._net_views(base, e, alive, net.q, 0.3, xp=np)  # [r, j, n]
        assert (np.moveaxis(views, -1, 0) == net._views(base, e)).all()


def test_the_lossless_mixed_program_keeps_its_operands_and_has_no_draws():
    import jax

    from rabia_tpu.apps.device_kv import DeviceKVTable, DeviceWindowOps

    n, R, w = 16, 5, 8
    ops = DeviceWindowOps(np.zeros((w, n), np.int16), np.zeros((w, n), np.int16),
                          np.zeros((w, n, 4), np.uint32), np.zeros((w, n, 16), np.uint32))
    args = (np.ones((n, R), bool), np.zeros(n, np.int32), np.int32(w),
            np.zeros((w, n), np.int8), np.zeros(8, np.int32), ops)
    texts = {}
    for loss in (0.0, 0.05):
        k = MeshPhaseKernel(n, R, make_mesh(), seed=3, net_loss=loss)
        t = DeviceKVTable(n, k, per_shard_capacity=8)
        if loss:
            low = t._build_mixed(4, 16, 8, net=True).lower(t.state, *args, W=w)
        else:
            low = t._build_mixed(4, 16, 8).lower(t.state, *args, W=w, max_phases=4)
        texts[loss] = low.as_text()
        # the table's seven planes, alive, base (or slots), depth, kind,
        # gidx and the four op planes: sixteen operands either way
        assert len(jax.tree_util.tree_leaves(low.args_info)) == 16
    net_start = str(mesh_mod._NET)  # the network chain's start, a constant
    assert net_start not in texts[0.0] and net_start in texts[0.05]


def _engine(loss, **kw):
    return MeshEngine(
        lambda: VectorShardedKV(8, capacity=1 << 10), n_shards=8, n_replicas=5,
        mesh=make_mesh(), window=4, device_store=kw.pop("device_store", True),
        device_store_kw={"per_shard_capacity": 16}, net_loss=loss, **kw,
    )


def test_engine_refuses_what_it_cannot_run_under_loss():
    for loss in (-0.1, 1.0, 1.5):
        with pytest.raises(ValidationError):
            _engine(loss)
    with pytest.raises(ValidationError):
        _engine(0.05, device_store=False)
    with pytest.raises(ValidationError):
        _engine(0.05, device_read_lane=True)
    eng = _engine(0.0)
    assert eng.net_loss == 0.0 and eng._dev_slots is None


def _set_block(n, w):
    from rabia_tpu.apps.kvstore import encode_set_bin
    from rabia_tpu.core.blocks import build_block

    return build_block(list(range(n)), [[encode_set_bin(f"k{s}", f"v{w}")] for s in range(n)])


def test_engine_under_loss_raises_where_it_would_demote_and_syncs_in_lane():
    eng = _engine(0.3)
    futs = [eng.submit_block(_set_block(8, w)) for w in range(8)]
    eng.flush()
    assert all(f.done() for f in futs) and eng.device_lane_active
    assert eng.decided_v0 > 0  # at 30 % loss some slots are null
    assert int(eng.next_slot.sum()) == 8 * 8 + eng.decided_v0
    eng.sync_to_host()  # a copy of the table: the lane stays
    assert eng.device_lane_active and all(len(sm.store) == 8 for sm in eng.sms)
    eng.submit(b"x", 0)  # a per-shard submission is outside the lane
    with pytest.raises(RabiaError, match="cannot demote"):
        eng.run_cycle()
    eng.close()


def test_lossless_engine_dispatches_the_lossless_program():
    eng = _engine(0.0)
    futs = [eng.submit_block(_set_block(8, w)) for w in range(4)]
    eng.flush()
    assert all(f.done() for f in futs)
    assert not any(k[0] == "mixnet" for k in eng._dev._fused_cache)
    assert eng.decided_v0 == 0 and int(eng.next_slot.sum()) == 4 * 8
    assert eng._net_phases.sum() == 0
    eng.close()
