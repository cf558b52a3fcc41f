"""Mesh-parallel ServeEngine (DESIGN.md §12).

The engine's mesh mode shards the slot pool over the mesh's data axes and
the weights tensor-parallel over 'model', with every jitted region's in/out
shardings pinned.  The contract is the §7 one, extended across devices:
sharding is INVISIBLE — a data-sharded engine must produce BYTE-identical
per-request streams to the single-device engine on the same workload (which
tier-1 already proves byte-identical to the sequential oracle), the tick
must still trace exactly once for the engine's life, and the data-parallel
hot path must compile to ZERO collective ops (proven on the tick's compiled
HLO via `dispatch.collective_ops`, plus `jax.debug` sharding inspection of
the live pool).

Workloads are the existing fuzz harness's seeded scenarios (a subset of the
21 seeds across the LSTM-packed and qwen3 families) with the slot count
overridden to divide the data axis — scenario slots of 1-3 can't shard
4-way, and slot count is schedule, not bytes (§7 per-request determinism).

Multi-device CPU needs XLA_FLAGS=--xla_force_host_platform_device_count=8
set BEFORE jax initializes (the dryrun.py pattern), which the tier-1
process can't do retroactively — so under a single device this file
re-runs ITSELF in a subprocess with the flag exported, and the real tests
run there (CI's tier-2 step exports the flag and runs them directly).
"""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_FORCED = "xla_force_host_platform_device_count" in os.environ.get(
    "XLA_FLAGS", "")


def test_make_host_mesh_rejects_nondivisor():
    """The old silent gcd-shrink is gone: a model axis that does not divide
    the device count raises and names the shape the fallback would have
    built (runs at ANY device count — 3 divides neither 1 nor 8)."""
    import jax
    from repro.launch.mesh import make_host_mesh

    n = len(jax.devices())
    assert n % 3, "test assumes a device count 3 does not divide"
    with pytest.raises(ValueError, match=r"does not divide"):
        make_host_mesh(model=3)
    with pytest.raises(ValueError, match=r"model=1"):
        # the message must NAME the resolved fallback shape (gcd(3, n)=1)
        make_host_mesh(model=3)
    mesh = make_host_mesh(model=1)
    assert mesh.shape == {"data": n}


def test_parse_mesh_spec():
    from repro.launch.mesh import parse_mesh_spec

    assert parse_mesh_spec("data=4,model=2") == {"data": 4, "model": 2}
    assert parse_mesh_spec("model=2") == {"data": 1, "model": 2}
    assert parse_mesh_spec("") == {"data": 1, "model": 1}
    with pytest.raises(ValueError, match="unknown mesh axis"):
        parse_mesh_spec("pod=2")
    with pytest.raises(ValueError, match="bad mesh spec"):
        parse_mesh_spec("data:4")


if not _FORCED:

    def test_mesh_suite_under_forced_devices():
        """Re-run this file under 8 forced host devices so plain tier-1
        proves mesh parity too (the flag must be set before jax's first
        backend init — impossible in-process here)."""
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(_REPO, "src"), env.get("PYTHONPATH", "")])
        r = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", os.path.abspath(__file__)],
            env=env, cwd=_REPO, capture_output=True, text=True)
        assert r.returncode == 0, (
            "mesh suite failed under forced devices:\n"
            + r.stdout[-6000:] + r.stderr[-2000:])

else:
    import dataclasses
    import random

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import bnlstm as BL
    from repro.core.qtensor import export_packed, is_qtensor
    from repro.core.quantize import QuantSpec
    from repro.kernels import dispatch
    from repro.launch.mesh import make_host_mesh, make_serve_mesh
    from repro.launch.sharding import serve_pool_shardings
    from repro.models import transformer as T
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.recurrent import (RNNRuntime, TransformerRuntime,
                                       speculative_draft)

    pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                    reason="needs 8 forced host devices")

    CTX = 32
    SLOTS = 8
    CHUNK = 4
    _RUNTIMES: dict = {}
    _ENGINES: dict = {}

    def _runtime(family):
        if family not in _RUNTIMES:
            if family == "lstm-packed":
                cfg = BL.RNNConfig(vocab=24, d_hidden=48, n_layers=2,
                                   cell="lstm",
                                   quant=QuantSpec(mode="ternary",
                                                   norm="batch"))
                var = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
                params = BL.export_packed_rnn(var["params"], cfg)
                rt = RNNRuntime(cfg, {"params": params,
                                      "state": var["state"]})
                _RUNTIMES[family] = (rt, cfg.vocab)
            elif family == "qwen3":
                cfg = get_config("qwen3-0.6b").reduced()
                rt = TransformerRuntime(
                    cfg, T.model_init(jax.random.PRNGKey(0), cfg))
                _RUNTIMES[family] = (rt, cfg.vocab)
            else:  # qwen3-packed: QTensor codes through serve shardings
                cfg = get_config("qwen3-0.6b").reduced().with_quant(
                    QuantSpec(mode="ternary", norm="channel"))
                params = export_packed(
                    T.model_init(jax.random.PRNGKey(0), cfg), cfg.quant)
                rt = TransformerRuntime(cfg, params)
                _RUNTIMES[family] = (rt, cfg.vocab)
        return _RUNTIMES[family]

    def _engine(family, mesh_spec):
        """Engines cached per (family, mesh) and reused across scenarios —
        the compile-once invariant is re-proven under workload churn, the
        same discipline as the fuzz harness."""
        key = (family, mesh_spec)
        if key not in _ENGINES:
            rt, vocab = _runtime(family)
            mesh = make_serve_mesh(mesh_spec) if mesh_spec else None
            _ENGINES[key] = ServeEngine(rt, vocab, slots=SLOTS,
                                        max_context=CTX,
                                        prefill_chunk=CHUNK, mesh=mesh)
        return _ENGINES[key]

    def _scenario_requests(seed, vocab):
        """The fuzz harness's request mix for a seed (same generator as
        tests/test_engine_fuzz._scenario; slots/chunk/eos draws are kept so
        the request stream matches that seed byte-for-byte, then ignored —
        mesh engines need slots divisible by the data axis)."""
        import test_engine_fuzz as fuzz

        reqs, _eos, _slots, _chunk = fuzz._scenario(seed, vocab)
        return reqs

    def _drain(eng, reqs):
        comps, _ = eng.run([dataclasses.replace(r) for r in reqs],
                           realtime=False)
        assert eng.tick_traces == 1
        return {c.rid: (c.tokens, c.finished) for c in comps}

    # -- BYTE parity: data-sharded == single-device --------------------------

    @pytest.mark.parametrize("seed", [100, 101, 103])
    def test_lstm_packed_data4_byte_parity(seed):
        rt, vocab = _runtime("lstm-packed")
        reqs = _scenario_requests(seed, vocab)
        assert _drain(_engine("lstm-packed", "data=4"), reqs) == \
            _drain(_engine("lstm-packed", ""), reqs)

    def test_lstm_packed_data8_byte_parity():
        rt, vocab = _runtime("lstm-packed")
        reqs = _scenario_requests(104, vocab)
        assert _drain(_engine("lstm-packed", "data=8"), reqs) == \
            _drain(_engine("lstm-packed", ""), reqs)

    @pytest.mark.parametrize("seed", [300, 302])
    def test_qwen3_data4_byte_parity(seed):
        rt, vocab = _runtime("qwen3")
        reqs = _scenario_requests(seed, vocab)
        assert _drain(_engine("qwen3", "data=4"), reqs) == \
            _drain(_engine("qwen3", ""), reqs)

    def test_spec_engine_data4_byte_parity():
        """Draft-verify-accept slot surgery is shard-aware too: a
        speculative mesh engine's streams match the single-device one."""
        cfg = BL.RNNConfig(vocab=24, d_hidden=48, n_layers=2, cell="lstm",
                           quant=QuantSpec(mode="none"))
        var = BL.rnn_lm_init(jax.random.PRNGKey(0), cfg)
        rt = RNNRuntime(cfg, {"params": var["params"],
                              "state": var["state"]})
        draft = speculative_draft(rt)
        reqs = [dataclasses.replace(r, temperature=0.0, top_k=0)
                for r in _scenario_requests(105, cfg.vocab)]
        streams = []
        for spec in ("", "data=4"):
            mesh = make_serve_mesh(spec) if spec else None
            eng = ServeEngine(rt, cfg.vocab, slots=SLOTS, max_context=CTX,
                              prefill_chunk=CHUNK, draft=draft, spec_k=3,
                              mesh=mesh)
            comps, _ = eng.run([dataclasses.replace(r) for r in reqs],
                               realtime=False)
            assert eng.spec_traces == 1
            streams.append({c.rid: c.tokens for c in comps})
        assert streams[0] == streams[1]

    # -- no resharding on the hot path ---------------------------------------

    @pytest.mark.parametrize("family", ["lstm-packed", "qwen3"])
    def test_data_sharded_tick_is_collective_free(family):
        """The data-parallel decode tick compiles to ZERO collective ops:
        rows are independent, weights are replicated, and the per-slot
        cache scatters are vmapped (index-parallel) — so N slot shards
        never add wire traffic.  (Tensor-parallel ticks legitimately
        reduce over 'model' and are not asserted here.)"""
        eng = _engine(family, "data=4")
        assert dispatch.collective_ops(eng.tick_hlo()) == []
        assert eng.tick_traces == 1  # tick_hlo restores the counters

    def test_pool_shardings_are_the_declared_ones():
        """The live pool's committed shardings match serve_pool_shardings
        (out-sharding pinning worked — nothing decayed to replicated), and
        jax.debug's sharding inspection sees the data axis on every
        slot-bearing leaf from INSIDE a jitted computation."""
        eng = _engine("lstm-packed", "data=4")
        expect = serve_pool_shardings(eng.pool, eng._refs[0], eng.mesh)
        for leaf, sh in zip(jax.tree_util.tree_leaves(eng.pool),
                            jax.tree_util.tree_leaves(
                                expect, is_leaf=lambda x: hasattr(x, "spec"))):
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim), (
                f"{leaf.sharding} != declared {sh}")

        seen = []

        def probe(pool):
            for leaf in jax.tree_util.tree_leaves(pool):
                jax.debug.inspect_array_sharding(leaf, callback=seen.append)
            return pool

        jax.jit(probe)(eng.pool)
        leaves = jax.tree_util.tree_leaves(eng.pool)
        declared = jax.tree_util.tree_leaves(
            expect, is_leaf=lambda x: hasattr(x, "spec"))
        assert len(seen) == len(leaves)
        for got, leaf, sh in zip(seen, leaves, declared):
            # the compiler may report a PositionalSharding; equivalence to
            # the declared NamedSharding is the assertion that matters
            assert got.is_equivalent_to(sh, leaf.ndim), (got, sh)

    # -- tensor parallelism (packed codes over 'model') ----------------------

    def test_qwen3_packed_tp_serves_with_sharded_codes():
        """data=2 x model=2 over a PACKED qwen3: the engine drains a fuzz
        workload with QTensor codes genuinely sharded along 'model' (column
        axis for up-projections, packed-row axis for down-projections).
        TP reorders the contraction's partial sums, so this is a liveness +
        layout proof, not a byte assert (that's the DP tests' job)."""
        rt, vocab = _runtime("qwen3-packed")
        eng = ServeEngine(rt, vocab, slots=SLOTS, max_context=CTX,
                          prefill_chunk=CHUNK,
                          mesh=make_serve_mesh("data=2,model=2"))
        comps, _ = eng.run(_scenario_requests(301, vocab), realtime=False)
        assert comps and eng.tick_traces == 1
        qleaves = [l for l in jax.tree_util.tree_leaves(
            eng._prms[0], is_leaf=is_qtensor) if is_qtensor(l)]
        assert qleaves
        specs = [str(q.codes.sharding.spec) for q in qleaves]
        assert any("model" in s for s in specs), specs

    # -- mesh construction + shard bookkeeping -------------------------------

    def test_make_host_mesh_on_eight_devices():
        assert make_host_mesh(model=2).shape == {"data": 4, "model": 2}
        with pytest.raises(ValueError, match=r"data=8,model=1"):
            make_host_mesh(model=3)

    def test_slots_must_divide_data_shards():
        rt, vocab = _runtime("lstm-packed")
        with pytest.raises(ValueError, match="split evenly"):
            ServeEngine(rt, vocab, slots=6, max_context=CTX,
                        prefill_chunk=CHUNK, mesh=make_serve_mesh("data=4"))

    def test_stats_report_per_shard_occupancy():
        eng = _engine("lstm-packed", "data=4")
        rt, vocab = _runtime("lstm-packed")
        rng = random.Random(7)
        for i in range(3):  # leave requests IN FLIGHT, then look
            eng.submit(Request(prompt=np.array([rng.randrange(vocab)],
                                               np.int32),
                               max_tokens=4, temperature=0.0, top_k=0,
                               seed=i))
        for _ in range(2):
            eng.step()
        s = eng.stats()
        assert s["mesh"] == {"data": 4, "model": 1}
        assert len(s["shards"]) == 4
        assert sum(sh["active"] for sh in s["shards"]) == s["active"] == 3
        # the shard-aware free-slot balancer spread 3 admissions over 3
        # different shards instead of piling onto shard 0
        assert sum(sh["active"] > 0 for sh in s["shards"]) == 3
        assert s["queue_depth"] == s["queued"]
        while eng.has_work():
            eng.step()
