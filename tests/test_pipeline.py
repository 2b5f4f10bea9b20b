"""GPipe pipeline: numerical equivalence to the sequential stack.

Runs in a subprocess with 4 forced host devices (the main test process
keeps the single-device view)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import bubble_fraction, gpipe

    mesh = jax.make_mesh((4,), ("stage",))
    S, M, mb, d = 4, 4, 2, 8     # small: compile time dominates on CPU

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    k = jax.random.key(0)
    params = {"w": jax.random.normal(k, (S, d, d)) * 0.5,
              "b": jnp.zeros((S, d))}
    xs = jax.random.normal(jax.random.key(1), (M, mb, d))

    piped = gpipe(stage_fn, mesh, "stage")
    with jax.set_mesh(mesh):
        got = jax.jit(piped)(params, xs)

    # sequential reference
    want = xs
    for s in range(S):
        want = jax.vmap(lambda x: stage_fn(
            {"w": params["w"][s], "b": params["b"][s]}, x))(want)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert abs(bubble_fraction(8, 4) - 3/11) < 1e-9
    print("PIPELINE_OK")
""")


@pytest.mark.slow
def test_gpipe_matches_sequential():
    # The child MUST pin JAX_PLATFORMS=cpu: without it jax probes the TPU
    # backend (libtpu ships in this image) and blocks for minutes before
    # falling back — the original stripped env dropped the variable and
    # died on TimeoutExpired. The forced 4-device view composes with cpu.
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=900, env=env)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
