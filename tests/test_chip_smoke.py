"""CPU rehearsal of ``chip_smoke.py``: its phase functions run end to end
at a tiny size (Pallas interpreted), the four-chip phases on four virtual
CPU devices, and the script itself refuses to report on a CPU."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

SCALE = 0.0005       # chicago at 2,665 nnz, shape (2048, 24, 77, 32)


def test_phases_run_on_cpu_at_tiny_size():
    tensor = chip_smoke.load_tensor(scale=SCALE)
    a = chip_smoke.phase_mttkrp(tensor, rank=8)
    for backend in chip_smoke.BACKENDS:
        assert a[backend]["max_rel_err"] <= chip_smoke.MTTKRP_TOL
    b = chip_smoke.phase_als(tensor, rank=8, iters=3)
    for backend in chip_smoke.BACKENDS:
        assert b[backend]["max_fit_gap"] <= chip_smoke.FIT_TOL
        assert b[backend]["host_syncs"] == 2      # one window + final
    tensors = chip_smoke.serve_stream(per_family=2)
    for backend in chip_smoke.BACKENDS:
        c = chip_smoke.phase_service(tensors, backend)
        assert c["requests"] == 4
        assert c["max_fit_gap"] <= chip_smoke.FIT_TOL


def test_mttkrp_reference_matches_dense():
    import numpy as np
    from repro.core import mttkrp_dense_ref, random_sparse

    t = random_sparse((9, 7, 5), 120, seed=3)
    rng = np.random.default_rng(0)
    factors = [rng.standard_normal((n, 4)).astype(np.float32)
               for n in t.shape]
    for d in range(3):
        np.testing.assert_allclose(
            chip_smoke.mttkrp_reference(t, factors, d, chunk=50),
            mttkrp_dense_ref(t, factors, d), rtol=1e-5, atol=1e-5)


def test_phase_failure_is_an_error():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke._check(False, "mismatch")


def _run(code: str, devices: int) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=600)


def test_four_chip_phases_on_virtual_devices():
    out = _run(f"""
        import jax, chip_smoke as cs
        from repro.launch.mesh import make_batch_mesh
        devices = jax.devices()[:4]
        tensors = cs.serve_stream(per_family=4)
        for backend in cs.BACKENDS:
            r = cs.phase_service(tensors, backend, mesh=make_batch_mesh(4))
            assert r["max_fit_gap"] <= cs.FIT_TOL, r
        r = cs.phase_distributed(cs.load_tensor(scale={SCALE}), devices,
                                 rank=8, iters=3)
        assert r["devices"] == 4 and r["max_fit_gap"] <= cs.FIT_TOL, r
        print("PASS")
    """, devices=4)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PASS" in out.stdout


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_script_refuses_cpu(argv):
    out = _run(f"""
        import sys, chip_smoke
        sys.exit(chip_smoke.main({argv!r}))
    """, devices=4)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert '"ok"' not in out.stdout
