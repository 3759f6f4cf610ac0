"""Scheduling-as-a-service (DESIGN.md §13).

The serving contract under test: K ragged requests packed into the `[B]`
cell axis of one compiled fused program are bit-for-bit the same
requests dispatched alone at B=1 — per scheduler, at any occupancy, with
padding cells never perturbing real cells, and each session's
server-side state (persistent fleet incl. the PR-5 P4 warm-start table,
model params) chaining across requests exactly as the solo run chains.
Plus the continuous-batching front-end: window packing, duplicate-
session deferral, latency metrics, and the in-process entrypoints.
"""
import asyncio
import importlib.util
import json
import math
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_no_retrace, mark_slow_unless

from repro.core.baselines import SCHEDULERS
from repro.core.scheduler import RolloutCarry
from repro.launch.serve import (BatchServer, SchedulingService,
                                ServeConfig, ServeRequest, SessionStore,
                                closed_loop_load, drive, poisson_load)
from repro.launch.serve import main as serve_main

L = 3           # compiled round horizon shared by most tests (one
#                 _fused_segment entry per (B, L) via the lru cache)


def _cfg(B, **kw):
    kw.setdefault("max_rounds", L)
    return ServeConfig(batch=B, **kw)


def _assert_same(a, b):
    """Responses bit-for-bit equal (the serving acceptance contract)."""
    assert a.n_rounds == b.n_rounds
    np.testing.assert_array_equal(a.success, b.success)
    np.testing.assert_array_equal(a.n_success, b.n_success)
    np.testing.assert_array_equal(a.loss, b.loss)


def test_padded_draws_factory_does_not_retrace():
    """reprolint retrace-budget pin: the host-packing draw-column
    factory (`_padded_draws`) compiles one program per (R, L, ...)
    shape and serves every seed from it — the shape here is distinct
    from every service config in this module so the pin measures a
    fresh executable."""
    from repro.launch.serve import _padded_draws
    fn = _padded_draws(3, 5, 9, 4, 6)
    with assert_no_retrace(fn, compiles=1):
        keys_a, _, _, active_a = fn(0)
        keys_b, _, _, _ = fn(1)
    assert keys_a.shape[0] == 5 and keys_b.shape[0] == 5
    np.testing.assert_array_equal(np.asarray(active_a),
                                  np.arange(5) < 3)


def _solo_replay(schedule, **cfg_kw):
    """Replay per-session request sequences on a fresh B=1 service —
    the reference every packed response must match bit-for-bit."""
    svc = SchedulingService(_cfg(1, **cfg_kw))
    return svc, {s: [svc.run_batch([r])[0] for r in reqs]
                 for s, reqs in schedule.items()}


@pytest.mark.parametrize("name,B", mark_slow_unless(
    [(n, b) for n in sorted(SCHEDULERS) for b in (1, 3)],
    {("madca", 1), ("madca", 3)}))
def test_packed_ragged_requests_match_solo(name, B):
    """K ragged requests packed into [B] cells are exact per scheduler:
    every packed response — and the second round of requests resuming
    each session's server-side state — is bit-for-bit the solo B=1
    run. Quick lane runs madca at both batch shapes; the full
    scheduler matrix is slow-lane."""
    kw = dict(scheduler=name, ipm_iters=4, ipm_warm_iters=2)
    svc = SchedulingService(_cfg(B, **kw))
    sessions = [f"s{i}" for i in range(B)]
    # ragged round counts, distinct seeds; a second wave resumes state
    waves = [[ServeRequest(s, 1 + (i + w) % L, seed=10 * w + i)
              for i, s in enumerate(sessions)] for w in range(2)]
    packed = [svc.run_batch(wave) for wave in waves]
    _, solo = _solo_replay(
        {s: [waves[0][i], waves[1][i]] for i, s in enumerate(sessions)},
        **kw)
    for w in range(2):
        for i, s in enumerate(sessions):
            _assert_same(packed[w][i], solo[s][w])


def test_padding_cells_never_perturb_real_cells():
    """An under-occupied batch pads spare cell slots with all-inactive
    replica cells: a request served at occupancy 1 of B=3 (2 padding
    cells) is bit-for-bit the same request at B=1, and the padding
    leaves no trace in the session store."""
    svc = SchedulingService(_cfg(3))
    reqs = [ServeRequest("only", L, seed=5), ServeRequest("only", 2, seed=6)]
    got = [svc.run_batch([r])[0] for r in reqs]
    _, solo = _solo_replay({"only": reqs})
    for g, s in zip(got, solo["only"]):
        _assert_same(g, s)
    assert set(svc.sessions) == {"only"}


def test_repeat_session_state_roundtrips_bitwise():
    """The session cache IS the serving state: after a packed request,
    the gathered-and-scattered per-session carry (fleet incl. p4_tab,
    params) equals the solo B=1 service's stored carry bit-for-bit."""
    svc = SchedulingService(_cfg(3))
    svc.run_batch([ServeRequest("a", L, seed=1),
                   ServeRequest("b", 2, seed=2)])
    ref, _ = _solo_replay({"a": [ServeRequest("a", L, seed=1)],
                           "b": [ServeRequest("b", 2, seed=2)]})
    for s in ("a", "b"):
        jax.tree.map(
            lambda x, y: np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y)),
            svc.sessions[s], ref.sessions[s])


def test_repeat_session_rides_warm_p4():
    """PR-5 warm path through the serving layer: with VEDS+COT and
    `ipm_warm_iters > 0`, a session's P4 warm-start table updates on the
    first request, the per-session scatter/gather of the table is
    bit-for-bit lossless (re-packing the unpacked sessions reproduces
    the dispatch's packed fleet exactly), and a second request's
    responses are bit-for-bit the solo B=1 warm run. The table itself is
    only compared to the B=1 run at tolerance: a batched lowering may sum
    the Newton step's dot products in another order at B=2 than at B=1,
    and Newton amplifies the last-ulp difference — the response-level
    contract is what stays bitwise. (On the CPU at these shapes the
    table comes out bitwise equal as well.) Tiny shapes keep the VEDS
    compile quick-lane affordable."""
    from repro.core.streaming import pack_cells
    kw = dict(max_rounds=2, scheduler="veds", n_sov=3, n_opv=2,
              n_slots=6, ipm_iters=4, ipm_warm_iters=2)
    svc = SchedulingService(ServeConfig(batch=2, **kw))
    tab0 = np.asarray(svc.session_carry("x").sched.p4_tab)
    reqs = {s: [ServeRequest(s, 2, seed=i), ServeRequest(s, 2, seed=i + 7)]
            for i, s in enumerate(("x", "y"))}
    captured = []
    orig = svc._seg[2]
    svc._seg[2] = lambda *a: captured.append(orig(*a)) or captured[-1]
    p1 = svc.run_batch([reqs["x"][0], reqs["y"][0]])
    tab1 = np.asarray(svc.sessions["x"].sched.p4_tab)
    assert not np.array_equal(tab1, tab0), "warm table never updated"
    # the session KV-cache contract: unpack -> store -> re-pack is the
    # identity on the dispatch's packed fleet (p4_tab included), bitwise
    repacked = pack_cells([svc.sessions[s].sched for s in ("x", "y")])
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), repacked, captured[-1].fleet)
    p2 = svc.run_batch([reqs["x"][1], reqs["y"][1]])
    ref = SchedulingService(ServeConfig(batch=1, **kw))
    s1 = [ref.run_batch([reqs[s][0]])[0] for s in ("x", "y")]
    np.testing.assert_allclose(
        tab1, np.asarray(ref.sessions["x"].sched.p4_tab), atol=1e-3)
    s2 = [ref.run_batch([reqs[s][1]])[0] for s in ("x", "y")]
    for i in range(2):
        _assert_same(p1[i], s1[i])
        _assert_same(p2[i], s2[i])


def test_run_batch_validation():
    svc = SchedulingService(_cfg(2))
    with pytest.raises(ValueError, match="cell slots"):
        svc.run_batch([ServeRequest(f"s{i}", 1) for i in range(3)])
    with pytest.raises(ValueError, match="duplicate sessions"):
        svc.run_batch([ServeRequest("s", 1), ServeRequest("s", 2)])
    with pytest.raises(ValueError, match="compiled horizon"):
        svc.run_batch([ServeRequest("s", L + 1)])
    with pytest.raises(ValueError, match="compiled horizon"):
        svc.run_batch([ServeRequest("s", 0)])


def test_per_cell_active_rejected_with_handoff():
    """The serving layer's per-cell no-op masks cannot compose with the
    cross-cell exchange — the engine must reject, not silently corrupt."""
    import dataclasses
    from repro.core.baselines import get_scheduler
    from repro.fl.engine import fused_rollout, init_carry
    from repro.launch.serve import default_problem, request_draws
    svc = SchedulingService(_cfg(2))
    cfg = dataclasses.replace(svc._stream, handoff=True)
    params, loss_fn, shards = default_problem()
    carry = init_carry(jax.random.key(0), svc.sc, svc.mob, cfg, params,
                       ch=svc.ch)
    keys, sel, mb_u = request_draws(jax.random.key(0), 2, 10, 4, 8)
    with pytest.raises(ValueError, match="handoff"):
        fused_rollout(keys, jnp.tile(sel[:, None], (1, 2, 1)),
                      jnp.tile(mb_u[:, None], (1, 2, 1, 1)),
                      get_scheduler("madca"), svc.sc, svc.mob, svc.ch,
                      svc.prm, cfg, loss_fn, shards, carry,
                      active=jnp.ones((2, 2), bool))


def test_per_cell_keys_rejected_in_fresh_fleet_mode():
    """Per-cell key batches need a persistent fleet — fresh-fleet mode
    draws the whole batch from one round key, so a [B] key layout would
    be silently misinterpreted."""
    import dataclasses
    from repro.core.baselines import get_scheduler
    from repro.core.streaming import sched_round_step
    from repro.core.streaming import _zero_carry
    svc = SchedulingService(_cfg(2))
    cfg = dataclasses.replace(svc._stream, fresh_fleet=True)
    with pytest.raises(ValueError, match="per-cell keys"):
        sched_round_step(_zero_carry(svc.sc, 2),
                         jax.random.split(jax.random.key(0), 2),
                         get_scheduler("madca"), svc.sc, svc.mob,
                         svc.ch, svc.prm, cfg)


def _serve(svc, coro_fn, **server_kw):
    async def go():
        async with BatchServer(svc, **server_kw) as srv:
            return await coro_fn(srv)
    return asyncio.run(go())


def test_batch_server_packs_within_window_and_records_metrics():
    """Five concurrent clients against B=3 under a wide window pack into
    two dispatches (occupancy 3 + 2); every response is bit-for-bit the
    solo replay, and the latency decomposition is sane."""
    svc = SchedulingService(_cfg(3))
    svc.warmup()
    reqs = [ServeRequest(f"c{i}", 1 + i % L, seed=i) for i in range(5)]

    async def load(srv):
        return await asyncio.gather(*(srv.submit(r) for r in reqs))

    got = _serve(svc, load, window_s=0.25)
    assert svc.metrics.occupancy == [3, 2]
    _, solo = _solo_replay({r.session: [r] for r in reqs})
    for r, g in zip(reqs, got):
        _assert_same(g, solo[r.session][0])
        assert g.total_s >= g.compute_s >= 0
        assert g.queue_wait_s >= 0
    s = svc.metrics.summary()
    assert s["n_requests"] == 5 and s["n_batches"] == 2
    assert s["mean_occupancy"] == pytest.approx(2.5)
    for k in ("p50_ms", "p99_ms", "rounds_per_s", "mean_queue_wait_ms",
              "mean_compute_ms"):
        assert math.isfinite(s[k]) and s[k] > 0, (k, s)


def test_batch_server_defers_duplicate_session_to_next_batch():
    """Two in-flight requests from ONE session must not co-occupy a
    batch (they would race on the session's state): the server defers
    the duplicate, and the pair still chains exactly like the solo
    sequential replay."""
    svc = SchedulingService(_cfg(3))
    svc.warmup()
    r1 = ServeRequest("dup", L, seed=1)
    r2 = ServeRequest("dup", 2, seed=2)
    other = ServeRequest("other", 1, seed=3)

    async def load(srv):
        return await asyncio.gather(srv.submit(r1), srv.submit(r2),
                                    srv.submit(other))

    g1, g2, go_ = _serve(svc, load, window_s=0.25)
    assert svc.metrics.occupancy == [2, 1]        # dup deferred
    _, solo = _solo_replay({"dup": [r1, r2], "other": [other]})
    _assert_same(g1, solo["dup"][0])
    _assert_same(g2, solo["dup"][1])
    _assert_same(go_, solo["other"][0])


def test_batch_server_buckets_rounds_by_horizon_rung():
    """Round-count-aware window formation: a window mixing 1-round and
    L-round requests on a (1, L) ladder splits by horizon rung before
    routing (shortest first), so the short requests stop paying the
    long rung's padded tail — pad_frac_rounds collapses to 0 for an
    exact-fit mix — and every response is still bit-for-bit the solo
    replay. `bucket_rounds=False` routes the same window whole to the
    max rung (the PR-8 behavior) and pays the padding."""
    kw = dict(tiers=(1, L), batch_tiers=(1, 3))
    reqs = [ServeRequest("a", 1, seed=1), ServeRequest("b", L, seed=2),
            ServeRequest("c", 1, seed=3)]

    async def load(srv):
        return await asyncio.gather(*(srv.submit(r) for r in reqs))

    svc = SchedulingService(_cfg(3, **kw))
    svc.warmup(rounds=(1, L))
    got = _serve(svc, load, window_s=0.25)
    assert svc.metrics.occupancy == [2, 1]      # rung 1 first, then L
    assert [g.tier for g in got] == ["L1xB3", f"L{L}xB1", "L1xB3"]
    assert svc.metrics.summary()["pad_frac_rounds"] == 0.0
    _, solo = _solo_replay({r.session: [r] for r in reqs})
    for r, g in zip(reqs, got):
        _assert_same(g, solo[r.session][0])

    flat = SchedulingService(_cfg(3, bucket_rounds=False, **kw))
    flat.warmup(rounds=(1, L))
    got_flat = _serve(flat, load, window_s=0.25)
    assert flat.metrics.occupancy == [3]        # one max-rung dispatch
    assert {g.tier for g in got_flat} == {f"L{L}xB3"}
    assert flat.metrics.summary()["pad_frac_rounds"] == \
        pytest.approx(1 - (1 + L + 1) / (3 * L))
    for r, g in zip(reqs, got_flat):
        _assert_same(g, solo[r.session][0])


def test_batch_server_failed_batch_fails_every_future():
    svc = SchedulingService(_cfg(2))
    svc.warmup()

    def boom(reqs):
        raise RuntimeError("scheduler down")

    svc.run_batch = boom

    async def load(srv):
        return await asyncio.gather(srv.submit(ServeRequest("a", 1)),
                                    srv.submit(ServeRequest("b", 1)),
                                    return_exceptions=True)

    out = _serve(svc, load, window_s=0.1)
    assert all(isinstance(e, RuntimeError) for e in out)


def test_serve_main_in_process(capsys):
    """The entrypoint takes explicit argv (no sys.argv mutation) and its
    --json output carries finite metrics."""
    argv_before = list(sys.argv)
    rc = serve_main(["--batch", "3", "--max-rounds", str(L),
                     "--clients", "3", "--requests", "1",
                     "--window-ms", "1", "--json"])
    assert rc == 0
    assert sys.argv == argv_before
    out = json.loads(capsys.readouterr().out)
    assert out["batched"]["n_requests"] == 3
    assert math.isfinite(out["speedup"]) and out["speedup"] > 0
    for k in ("p50_ms", "p99_ms", "rounds_per_s", "mean_occupancy"):
        assert math.isfinite(out["batched"][k]), out


def test_example_entrypoint_in_process(capsys):
    """examples/serve_batch.py is importable and runs in-process with
    explicit argv; exit code 0 certifies its own packed-vs-solo
    bit-for-bit check."""
    path = (pathlib.Path(__file__).parent.parent / "examples"
            / "serve_batch.py")
    spec = importlib.util.spec_from_file_location("serve_batch_example",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    argv_before = list(sys.argv)
    rc = mod.main(["--clients", "3", "--requests", "1", "--batch", "3",
                   "--rounds", str(L), "--window-ms", "1"])
    assert rc == 0
    assert sys.argv == argv_before
    assert "(bit-for-bit): True" in capsys.readouterr().out


def _assert_carry_equal(a, b):
    """Two RolloutCarry pytrees bitwise equal (device or host leaves)."""
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(
        np.asarray(x), np.asarray(y)), a, b)


# ---------------------------------------------------------------------------
# Horizon/occupancy tiering: routing, exactness across tiers, padding
# accounting, and the compile-cache contract.

def test_tiered_routing_picks_smallest_tier_and_stays_bitwise():
    """With a (1, L) horizon ladder and explicit (1, B) occupancy
    buckets, each batch routes to the smallest rung that fits — and
    every response, including a session resuming across DIFFERENT tiers,
    is bit-for-bit the single-tier solo B=1 replay."""
    kw = dict(tiers=(1, L), batch_tiers=(1, 3))
    svc = SchedulingService(_cfg(3, **kw))
    r1 = ServeRequest("a", 1, seed=1)            # -> tier (L=1, B=1)
    wave = [ServeRequest("a", L, seed=2),        # -> tier (L=3, B=3)
            ServeRequest("b", 2, seed=3),
            ServeRequest("c", 1, seed=4)]
    p1 = svc.run_batch([r1])
    p2 = svc.run_batch(wave)
    assert dict(svc.metrics.tier_hits) == {"L1xB1": 1, f"L{L}xB3": 1}
    # each response records the executable that served it
    assert p1[0].tier == "L1xB1"
    assert {r.tier for r in p2} == {f"L{L}xB3"}
    _, solo = _solo_replay({"a": [r1, wave[0]], "b": [wave[1]],
                            "c": [wave[2]]})
    _assert_same(p1[0], solo["a"][0])
    _assert_same(p2[0], solo["a"][1])
    _assert_same(p2[1], solo["b"][0])
    _assert_same(p2[2], solo["c"][0])
    s = svc.metrics.summary()
    # dispatch 1: 1/1 active; dispatch 2: (3+2+1)/9 round-slots active
    assert s["pad_frac_rounds"] == pytest.approx(1 - 7 / 10)
    assert s["pad_frac_cells"] == 0.0
    # single-tier accounting of the same load pads everything to [L, B]
    ref = SchedulingService(_cfg(3))
    ref.run_batch([r1])
    ref.run_batch(wave)
    assert ref.metrics.summary()["pad_frac_rounds"] == \
        pytest.approx(1 - 7 / 12)
    assert ref.metrics.summary()["pad_frac_cells"] == \
        pytest.approx(1 - 4 / 6)


def test_tier_ladder_validation():
    with pytest.raises(ValueError, match="batch_tiers"):
        SchedulingService(_cfg(3, batch_tiers=(1, 2)))   # max != batch
    with pytest.raises(ValueError, match="tiers"):
        SchedulingService(_cfg(3, tiers=(0, 3)))
    svc = SchedulingService(_cfg(3, tiers=(1, L)))
    with pytest.raises(ValueError, match="compiled horizon"):
        svc.run_batch([ServeRequest("s", L + 1)])


def test_tier_executables_share_the_engine_segment_cache():
    """The compile-cache contract (DESIGN.md §13): one segment-cache
    entry per occupancy tier, shared with ANY caller that builds the
    same key — two services with the same workload/shape reuse the same
    jitted segment objects instead of re-tracing."""
    import dataclasses
    from repro.fl.engine import fused_segment
    svc = SchedulingService(_cfg(3, tiers=(1, L), batch_tiers=(1, 3)))
    assert sorted(svc._seg) == [1, 3]
    twin = SchedulingService(_cfg(3, tiers=(1, L), batch_tiers=(1, 3)))
    for b in (1, 3):
        assert svc._seg[b] is twin._seg[b]
        assert svc._seg[b] is fused_segment(
            svc.loss_fn, svc.cfg.scheduler, svc.sc, svc.mob, svc.ch,
            svc.prm, dataclasses.replace(svc._stream, batch=b),
            svc.cfg.lr, 1, None, 1)


def test_b4_dispatch_is_deterministic_per_executable():
    """The occupancy-invariance boundary (DESIGN.md §13): B > 1
    executables may fuse differently from the B=1 program on XLA CPU,
    so packed bits are only pinned against solo at small shapes and at
    occupancy 1 — but every executable is deterministic: replaying the
    identical dispatch sequence on a fresh service reproduces every
    response bit-for-bit."""
    reqs = [ServeRequest(f"s{j}", L, seed=j) for j in range(4)]
    runs = []
    for _ in range(2):
        svc = SchedulingService(_cfg(4))
        runs.append(svc.run_batch(reqs) + svc.run_batch(
            [ServeRequest(f"s{j}", L - 1, seed=10 + j) for j in range(4)]))
    for a, b in zip(*runs):
        assert a.tier == b.tier
        _assert_same(a, b)


def test_pack_cells_pad_to():
    """`pack_cells(pad_to=)`: spare tier slots are replicas of the first
    state; padding below the live count is rejected."""
    from repro.core.streaming import pack_cells, unpack_cell
    a = {"x": jnp.arange(4.0).reshape(1, 4)}
    b = {"x": 1.0 + jnp.arange(4.0).reshape(1, 4)}
    packed = pack_cells([a, b], pad_to=4)
    assert packed["x"].shape == (4, 4)
    _assert_carry_equal(unpack_cell(packed, 0), a)
    _assert_carry_equal(unpack_cell(packed, 1), b)
    _assert_carry_equal(unpack_cell(packed, 2), a)
    _assert_carry_equal(unpack_cell(packed, 3), a)
    with pytest.raises(ValueError, match="pad_to"):
        pack_cells([a, b], pad_to=1)


# ---------------------------------------------------------------------------
# Bounded session cache: LRU order, spill/restore bitwise, concurrency.

def test_session_store_lru_spill_and_bitwise_restore():
    """Pure store semantics: the LRU carry past `max_sessions` spills to
    host numpy; a touch restores it bitwise and re-evicts the new LRU."""
    def carry(v):
        return RolloutCarry(sched={"t": jnp.full((2, 3), v)},
                            params={"w": jnp.full((1, 4), 10.0 * v)},
                            opt_state=None)

    store = SessionStore(max_sessions=2)
    vals = {s: carry(float(i)) for i, s in enumerate("abc")}
    for s in "abc":
        store.put(s, vals[s])
    assert (store.n_device, store.n_spilled, len(store)) == (2, 1, 3)
    assert list(store._hot) == ["b", "c"] and "a" in store
    # spilled leaves live on host (numpy), hot leaves on device
    assert isinstance(store._spilled["a"].sched["t"], np.ndarray)
    got = store.get("a")                    # restore -> evicts b
    assert isinstance(got.sched["t"], jnp.ndarray)
    _assert_carry_equal(got, vals["a"])
    assert list(store._hot) == ["c", "a"] and "b" in store
    store.get("c")                          # refresh c -> LRU is now a
    store.put("d", carry(3.0))
    assert list(store._hot) == ["c", "d"]
    _assert_carry_equal(store["a"], vals["a"])   # restore via getitem
    assert store.pop("zzz", None) is None
    assert store.pop("d") is not None and "d" not in store
    assert set(store) == {"a", "b", "c"}
    with pytest.raises(ValueError, match="max_sessions"):
        SessionStore(max_sessions=0)


def test_evicted_session_resumes_bitwise_with_warm_p4():
    """Evict -> restore roundtrip through real dispatches, on the
    hardest carry: VEDS with a live warm `p4_tab`. Session x's table
    updates on its first request, spills to host when y and z arrive,
    and x's next request — served from the restored carry — responds
    AND stores bit-for-bit like the never-evicted service."""
    kw = dict(max_rounds=2, scheduler="veds", n_sov=3, n_opv=2,
              n_slots=6, ipm_iters=4, ipm_warm_iters=2)
    reqs = {s: [ServeRequest(s, 2, seed=i), ServeRequest(s, 1, seed=i + 7)]
            for i, s in enumerate(("x", "y", "z"))}
    svc = SchedulingService(ServeConfig(batch=1, max_sessions=1, **kw))
    ref = SchedulingService(ServeConfig(batch=1, **kw))
    for s in ("x", "y", "z"):
        svc.run_batch([reqs[s][0]])
        ref.run_batch([reqs[s][0]])
    assert svc.sessions.n_device == 1 and svc.sessions.n_spilled == 2
    tab_hot = np.asarray(ref.sessions["x"].sched.p4_tab)
    tab_cold = svc.sessions._spilled["x"].sched.p4_tab
    np.testing.assert_array_equal(tab_cold, tab_hot)
    got = svc.run_batch([reqs["x"][1]])[0]        # restores x, evicts z
    want = ref.run_batch([reqs["x"][1]])[0]
    _assert_same(got, want)
    _assert_carry_equal(svc.sessions["x"], ref.sessions["x"])
    assert svc.metrics.n_spills >= 3 and svc.metrics.n_restores == 1
    assert ref.metrics.n_spills == 0 and ref.metrics.n_restores == 0


def test_max_sessions_enforced_under_concurrent_submits():
    """Device-resident sessions stay bounded (flat in session count)
    while many concurrent clients hammer the server — every spilled
    session still answers correctly when it comes back."""
    svc = SchedulingService(_cfg(3, max_sessions=2))
    svc.warmup()

    async def load(srv):
        return await closed_loop_load(srv, n_clients=6, n_requests=2,
                                      n_rounds=2, seed=3)

    got = _serve(svc, load, window_s=0.01)
    assert len(got) == 12
    assert svc.sessions.n_device <= 2
    assert len(svc.sessions) == 6
    assert svc.metrics.n_spills >= 4
    # second-wave responses chained through spill/restore: replay two
    # sessions' sequences on an UNBOUNDED solo service
    _, solo = _solo_replay({
        s: [ServeRequest(s, 2, seed=3 + 1000 * c + i) for i in range(2)]
        for c, s in [(0, "client-0"), (5, "client-5")]})
    by_sess = {}
    for r in got:
        by_sess.setdefault(r.session, []).append(r)
    for s in ("client-0", "client-5"):
        for g, w in zip(by_sess[s], solo[s]):
            _assert_same(g, w)


# ---------------------------------------------------------------------------
# BatchServer deferral fairness.

def test_deferred_request_is_served_fifo_first_next_batch():
    """Starvation regression: a deferred duplicate-session request must
    seed the NEXT batch, ahead of newer arrivals — not re-enter the
    back of the queue where fresh traffic keeps displacing it."""
    svc = SchedulingService(_cfg(3))
    svc.warmup()
    batches = []
    orig = svc.run_batch
    svc.run_batch = lambda reqs: batches.append(
        [r.session for r in reqs]) or orig(reqs)
    a1, a2 = ServeRequest("A", 1, seed=1), ServeRequest("A", 1, seed=2)
    others = [ServeRequest(f"o{i}", 1, seed=3 + i) for i in range(4)]

    async def load(srv):
        return await asyncio.gather(
            srv.submit(a1), srv.submit(a2),
            *(srv.submit(o) for o in others))

    got = _serve(svc, load, window_s=0.25, max_batch=2)
    # batch 1 takes A#1 + o0 (A#2 deferred); the deferred A#2 must lead
    # batch 2 — the old tail-requeue would have served o1..o3 first
    assert batches[0] == ["A", "o0"]
    assert batches[1][0] == "A"
    assert [len(b) for b in batches] == [2, 2, 2]
    _, solo = _solo_replay({"A": [a1, a2],
                            **{o.session: [o] for o in others}})
    _assert_same(got[0], solo["A"][0])
    _assert_same(got[1], solo["A"][1])
    for o, g in zip(others, got[2:]):
        _assert_same(g, solo[o.session][0])


@pytest.mark.slow
def test_tiered_routing_sustains_1p3x_on_mixed_poisson_load():
    """Acceptance, two phases. (1) Throughput at full occupancy: on a
    mixed n_rounds in {4..64} Poisson load, routing each window to the
    smallest fitting (horizon x occupancy) tier sustains >= 1.3x the
    aggregate rounds/s of the single-L=64 service at batch=8. (2)
    Exactness of horizon routing: the same mixed load served at
    batch=1 through the full horizon ladder is bit-for-bit the solo
    single-tier replay for EVERY response — the L axis only changes
    the scan trip count, never the compiled round program. The B axis
    is different: B>1 executables fuse/tile differently on XLA CPU and
    their float bits can drift from B=1 at large shapes (params at
    L64xB2, virtual queues at B>=4 — pre-existing since the single
    B=8 executable of the previous PR; DESIGN.md §13), which is why
    the bitwise sweep pins occupancy 1 while the throughput sweep runs
    the full B=8 ladder."""
    mix = (4, 8, 4, 16, 8, 64)            # mostly short, worst case 64

    def run(tiers, batch=8, **cfg_kw):
        cfg = ServeConfig(batch=batch, max_rounds=64, tiers=tiers,
                          window_s=2e-3, **cfg_kw)
        svc = SchedulingService(cfg)
        svc.warmup(rounds=mix)

        async def go():
            async with BatchServer(svc) as srv:
                return await poisson_load(srv, n_clients=8, rate_hz=400.0,
                                          n_requests=6, n_rounds=mix,
                                          seed=0)

        resp = asyncio.run(go())
        return svc.metrics.summary(), resp

    # --- phase 1: throughput, full B=8 occupancy ladder ---
    tiered, resp = run((8, 16, 64))
    single, _ = run(None)
    speedup = tiered["rounds_per_s"] / single["rounds_per_s"]
    assert speedup >= 1.3, (speedup, tiered, single)
    assert tiered["pad_frac_rounds"] < single["pad_frac_rounds"]
    assert len(tiered["tier_hits"]) > 1, tiered

    # --- phase 2: exactness of horizon routing, occupancy pinned at 1 ---
    exact, resp = run((8, 16, 64), batch=1)
    assert len(exact["tier_hits"]) > 1, exact
    assert all(r.tier.endswith("xB1") for r in resp)
    # replay every session's request sequence on a fresh single-tier
    # solo B=1 service
    schedule = {}
    for r in resp:
        c = int(r.session.split("-")[1])
        i = len(schedule.setdefault(r.session, []))
        schedule[r.session].append(
            ServeRequest(r.session, r.n_rounds, seed=1000 * c + i))
    _, solo = _solo_replay(schedule, max_rounds=64)
    # responses keep per-client submission order, so zip lines up
    for s, seq in schedule.items():
        packed = [r for r in resp if r.session == s]
        for g, w in zip(packed, solo[s]):
            _assert_same(g, w)


@pytest.mark.slow
def test_batched_serving_sustains_2x_rounds_per_s():
    """Acceptance: under saturating closed-loop load from 8 concurrent
    clients, the batched server sustains >= 2x the aggregate rounds/s of
    sequential B=1 dispatch on CPU (the packed program amortizes both
    dispatch and per-round overhead across the cell axis)."""
    cfg = ServeConfig(batch=8, max_rounds=4, window_s=5e-4)
    out = drive(cfg, n_clients=8, n_requests=8, baseline=True, seed=0)
    assert out["batched"]["mean_occupancy"] > 4.0, out
    assert out["speedup"] >= 2.0, out
