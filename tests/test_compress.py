"""Error-feedback int8 gradient compression (cross-pod wire format)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.optim.compress import compress_pod_gradients, ef_init


def test_single_pod_identity_path():
    """Outside a bound axis: quantize/dequantize only, error captured."""
    rng = np.random.default_rng(0)
    g = {"w": jnp.asarray(rng.standard_normal(300), jnp.float32)}
    ef = ef_init(g)
    out, ef2 = compress_pod_gradients(g, ef)
    err = np.asarray(g["w"] - out["w"])
    # per-block error bound: absmax/127
    assert np.abs(err).max() <= float(jnp.abs(g["w"]).max()) / 127 + 1e-7
    # the residual exactly accounts for the loss
    np.testing.assert_allclose(np.asarray(out["w"] + ef2["w"]),
                               np.asarray(g["w"]), atol=1e-6)


def test_error_feedback_unbiased_over_steps():
    """Constant gradient: with EF, the running mean of compressed grads
    converges to the true gradient (the EF guarantee)."""
    rng = np.random.default_rng(1)
    g_true = {"w": jnp.asarray(rng.standard_normal(256) * 1e-3
                               + np.where(rng.random(256) < 0.1, 1.0, 0.0),
                               jnp.float32)}
    ef = ef_init(g_true)
    acc = np.zeros(256)
    steps = 50
    for _ in range(steps):
        out, ef = compress_pod_gradients(g_true, ef)
        acc += np.asarray(out["w"])
    np.testing.assert_allclose(acc / steps, np.asarray(g_true["w"]),
                               atol=2e-3)


def test_cross_pod_psum():
    """Under shard_map with a bound 'pod' axis the payloads psum."""
    import subprocess
    import sys
    import textwrap
    import os
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = "src"
    code = textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.compress import compress_pod_gradients, ef_init
        mesh = jax.make_mesh((2,), ('pod',))
        g = jnp.stack([jnp.arange(256, dtype=jnp.float32) / 64.0,
                       -jnp.arange(256, dtype=jnp.float32) / 128.0])

        def body(gl):
            gl = gl[0]
            out, ef = compress_pod_gradients({'w': gl}, ef_init({'w': gl}),
                                             axis='pod')
            return out['w'][None]

        f = shard_map(body, mesh=mesh, in_specs=P('pod'),
                      out_specs=P('pod'), check_vma=False)
        out = jax.jit(f)(g)
        want = np.asarray(g).mean(0)
        got = np.asarray(out)[0]
        assert np.abs(got - want).max() < 0.05, np.abs(got - want).max()
        print('OK')
    """)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd="/root/repo")
    assert p.returncode == 0, p.stderr[-2000:]
    assert "OK" in p.stdout
