"""Multi-device tests (subprocess: jax device count is locked at first init,
so each test spawns a fresh interpreter with forced host devices)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_distributed_mttkrp_both_schemes():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import random_sparse, mttkrp_dense_ref
        from repro.core.distributed import make_distributed_plan, mttkrp_distributed
        t = random_sparse((64, 40, 3), 1500, seed=2, distribution="powerlaw")
        rng = np.random.default_rng(0)
        factors = [jnp.asarray(rng.standard_normal((I, 8)).astype(np.float32))
                   for I in t.shape]
        plan = make_distributed_plan(t)
        for d in range(3):
            ref = mttkrp_dense_ref(t, [np.asarray(f) for f in factors], d)
            got = np.asarray(mttkrp_distributed(plan, factors, d))
            err = np.abs(got - ref).max()
            assert err < 1e-3, (d, err)
            print("mode", d, plan.modes[d].scheme.name, "ok")
        print("PASS")
    """)
    assert "PASS" in out
    assert "NNZ_PARTITION" in out and "INDEX_PARTITION" in out


def test_distributed_cpd_runs():
    out = run_py("""
        from repro.core import random_sparse
        from repro.core.distributed import cpd_als_distributed
        t = random_sparse((48, 32, 16), 1200, seed=3, distribution="powerlaw")
        res = cpd_als_distributed(t, rank=4, n_iters=4)
        assert res.engine == "distributed"
        assert len(res.fits) >= 1 and res.fits[-1] > 0
        print("PASS", res.fits[-1])
    """)
    assert "PASS" in out


def test_distributed_fused_matches_single_device():
    """The shard_map fused sweep (psum of partial MTTKRPs, one dispatch
    per check window) matches single-device cpd_als to fp32 tolerance on
    an 8-virtual-device mesh; with the tolerance off it fetches no fit
    between windows and syncs once, at the read-back."""
    out = run_py("""
        import numpy as np
        from repro.core import cpd_als, random_sparse
        from repro.core.distributed import cpd_als_distributed
        from repro.obs.ledger import LEDGER
        # mode 2 has I_d = 6 < 8 devices -> scheme 2 (overlapping partials);
        # modes 0/1 are scheme 1 (disjoint partials): one psum sweep serves
        # both load-balancing schemes.
        t = random_sparse((48, 32, 6), 1500, seed=5, distribution="powerlaw")
        ref = cpd_als(t, rank=4, n_iters=6, tol=-1.0, seed=2)
        LEDGER.reset()
        res = cpd_als_distributed(t, rank=4, n_iters=6, tol=-1.0, seed=2,
                                  check_every=3)
        np.testing.assert_allclose(res.fits, ref.fits, rtol=1e-4, atol=1e-4)
        for Fd, Fr in zip(res.factors, ref.factors):
            np.testing.assert_allclose(Fd, Fr, rtol=1e-3, atol=1e-3)
        assert res.host_syncs == 1, res.host_syncs
        # The fit counts under the mesh executable's own ledger kind.
        c = LEDGER.counts("dist_block")
        assert c["dispatches"] == 2 and c["h2d_bytes"] > 0, c
        assert LEDGER.counts("sweep_block")["dispatches"] == 0
        print("PASS", res.fits[-1], res.host_syncs)
    """)
    assert "PASS" in out


def test_distributed_cp_fit_is_read_once_from_the_combined_mttkrp():
    """The CP fit reads <X, X_hat> from the last mode's MTTKRP after its
    cross-device combine, so it is global with no psum of its own: the
    8-device fit history equals the single-device fused run's under both
    collectives (a second psum would scale the inner product by 8), and
    the sweep's trace counts the MTTKRP-read fit."""
    out = run_py("""
        import numpy as np
        from repro.core import cpd_als_fused, random_sparse
        from repro.core.distributed import cpd_als_distributed
        from repro.obs.ledger import LEDGER

        t = random_sparse((40, 30, 12, 6), 1800, seed=8,
                          distribution="powerlaw")
        ref = cpd_als_fused(t, rank=4, n_iters=5, tol=-1.0, seed=3,
                            check_every=5)
        assert ref.fits[-1] > 0.01, ref.fits
        for collective in ("psum", "gather"):
            LEDGER.reset()
            res = cpd_als_distributed(t, rank=4, n_iters=5, tol=-1.0,
                                      seed=3, check_every=5,
                                      collective=collective)
            np.testing.assert_allclose(res.fits, ref.fits, rtol=1e-4,
                                       atol=1e-4)
            c = LEDGER.counts("sweep_fit")
            assert c.get("mttkrp", 0) >= 1 and c.get("gather", 0) == 0, c
        print("PASS", ref.fits[-1])
    """)
    assert "PASS" in out


def test_elastic_restore_different_topology(tmp_path):
    """Checkpoint on an 8-device mesh, restore onto 4 devices."""
    code1 = f"""
        import jax, jax.numpy as jnp
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config, reduce_config
        from repro.models import get_model
        from repro.launch.mesh import make_host_mesh
        from repro.launch import shardings as shd
        cfg = reduce_config(get_config("minitron-4b"))
        model = get_model(cfg)
        mesh = make_host_mesh((4, 2), ("data", "model"))
        p_shard = shd.param_shardings(model, mesh)
        with mesh:
            params = jax.jit(model.init, out_shardings=p_shard)(jax.random.PRNGKey(0))
        m = CheckpointManager(r"{tmp_path}", async_save=False)
        m.save(1, params)
        print("SAVED", len(jax.devices()))
    """
    out1 = run_py(code1, devices=8)
    assert "SAVED 8" in out1

    code2 = f"""
        import numpy as np, jax
        from repro.checkpoint import CheckpointManager
        from repro.configs import get_config, reduce_config
        from repro.models import get_model
        from repro.launch.mesh import make_host_mesh
        from repro.launch import shardings as shd
        cfg = reduce_config(get_config("minitron-4b"))
        model = get_model(cfg)
        mesh = make_host_mesh((2, 2), ("data", "model"))
        p_shard = shd.param_shardings(model, mesh)
        m = CheckpointManager(r"{tmp_path}")
        params, _ = m.restore(template=model.abstract_params(), shardings=p_shard)
        devs = {{d.id for leaf in jax.tree.leaves(params)
                for d in leaf.sharding.device_set}}
        assert len(jax.devices()) == 4
        # run a forward step on the restored params to prove usability
        import jax.numpy as jnp
        toks = jnp.zeros((2, 8), jnp.int32)
        with mesh:
            logits, _ = model.forward(params, toks)
        assert bool(jnp.all(jnp.isfinite(logits)))
        print("RESTORED", len(devs))
    """
    out2 = run_py(code2, devices=4)
    assert "RESTORED 4" in out2


def test_compressed_crosspod_mean_matches_exact():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.optim.compress import cross_pod_mean
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        g = {"w": jnp.asarray(rng.standard_normal((32, 16)).astype(np.float32))}
        err = {"w": jnp.zeros((32, 16))}
        exact, _ = cross_pod_mean(g, err, mesh, compress=False)
        comp, new_err = cross_pod_mean(g, err, mesh, compress=True)
        rel = float(jnp.abs(comp["w"] - exact["w"]).max()
                    / jnp.abs(exact["w"]).max())
        assert rel < 0.02, rel
        # residual is exactly the quantization error
        assert float(jnp.abs(new_err["w"]).max()) > 0
        print("PASS", rel)
    """, devices=4)
    assert "PASS" in out


def test_train_step_shards_on_2d_mesh():
    out = run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import optim
        from repro.configs import get_config, reduce_config
        from repro.data import TokenPipeline
        from repro.launch.mesh import make_host_mesh
        from repro.launch import shardings as shd, steps as steps_mod
        from repro.models import get_model
        cfg = reduce_config(get_config("dbrx-132b"))
        model = get_model(cfg)
        mesh = make_host_mesh((2, 4), ("data", "model"))
        p_shard = shd.param_shardings(model, mesh)
        o_shard = shd.opt_state_shardings(p_shard, mesh)
        step = steps_mod.make_train_step(model, optim.AdamWConfig(lr=3e-3, warmup_steps=1, total_steps=50))
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, None),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        with mesh:
            params = jax.jit(model.init, out_shardings=p_shard)(jax.random.PRNGKey(0))
            opt = jax.jit(optim.init_state, out_shardings=o_shard)(params)
            pipe = TokenPipeline(cfg.vocab_size, batch=4, seq_len=16, seed=0)
            losses = []
            for _ in range(10):
                b = {k: jnp.asarray(v) for k, v in next(pipe).items()}
                params, opt, m = jitted(params, opt, b)
                losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0]
        print("PASS", losses)
    """, devices=8)
    assert "PASS" in out


def test_gather_collective_matches_psum():
    """Scheme-1 (index-partitioned) modes can skip the full-array psum:
    each device all-gathers only its owned row slice (plus the int32
    destination map) and scatters locally.  The gather run must agree
    with the psum run to fp32, and its recorded collective payload must
    be strictly smaller on every index-partitioned mode."""
    out = run_py("""
        import numpy as np
        from repro.core import random_sparse
        from repro.core.distributed import (
            cpd_als_distributed, collective_payload_bytes,
            make_distributed_plan, resolve_collectives)

        t = random_sparse((64, 48, 32), 2000, seed=4,
                          distribution="powerlaw")
        for method in ("cp", "nncp"):
            a = cpd_als_distributed(t, rank=4, n_iters=5, tol=-1.0, seed=2,
                                    check_every=5, method=method)
            b = cpd_als_distributed(t, rank=4, n_iters=5, tol=-1.0, seed=2,
                                    check_every=5, method=method,
                                    collective="gather")
            np.testing.assert_allclose(b.fits, a.fits, rtol=1e-4, atol=1e-4)
            for Fa, Fb in zip(a.factors, b.factors):
                np.testing.assert_allclose(Fb, Fa, rtol=1e-3, atol=1e-3)

        plan = make_distributed_plan(t)
        cols = resolve_collectives(plan, "gather")
        assert cols is not None and "gather" in cols
        psum_b = collective_payload_bytes(plan, 4, None)
        gath_b = collective_payload_bytes(plan, 4, cols)
        assert gath_b < psum_b, (gath_b, psum_b)
        print("PASS", cols, psum_b, gath_b)
    """)
    assert "PASS" in out


def test_gather_collective_rejects_valued_plans():
    """The gather scatter would drop the padding-row values the masked
    (valued) layout needs, so resolving 'gather' for a weighted plan is a
    hard error instead of silent wrongness."""
    out = run_py("""
        import numpy as np
        from repro.core import random_sparse
        from repro.core.distributed import cpd_als_distributed

        t = random_sparse((48, 32, 16), 1200, seed=6,
                          distribution="powerlaw")
        w = np.random.default_rng(0).uniform(
            0.25, 1.75, t.nnz).astype(np.float32)
        try:
            cpd_als_distributed(t, rank=4, n_iters=2, method="masked",
                                weights=w, collective="gather")
        except ValueError as e:
            print("PASS", e)
        else:
            raise AssertionError("gather accepted a valued plan")
    """)
    assert "PASS" in out
