"""Pipeline-parallel correctness: GPipe over N fake devices must equal the
serial layer stack, for forward AND gradients. Runs in a subprocess so
the 1-device default of the rest of the suite is untouched; the child is
pinned to the CPU so it can never reach for an accelerator."""
import os
import subprocess
import sys
import textwrap


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import pipeline_apply, split_stages

    mesh = jax.make_mesh((4,), ("pod",))
    L, D = 8, 16
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (L, D, D)) * 0.3

    def layer_block(params, x):     # params: (L/4, D, D)
        def body(h, w):
            return jnp.tanh(h @ w), None
        y, _ = jax.lax.scan(body, x, params)
        return y

    mbs = jax.random.normal(jax.random.PRNGKey(1), (6, 5, D))

    # serial reference
    def serial(Ws, mbs):
        def all_layers(x):
            return layer_block(Ws, x)
        return jax.vmap(all_layers)(mbs)

    want = serial(Ws, mbs)
    stage_params = split_stages(Ws, 4)
    got = jax.jit(lambda p, m: pipeline_apply(mesh, "pod", layer_block, p, m))(
        stage_params, mbs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    # gradient parity
    def loss_pipe(p, m):
        return jnp.sum(pipeline_apply(mesh, "pod", layer_block, p, m) ** 2)

    def loss_serial(w, m):
        return jnp.sum(serial(w, m) ** 2)

    g_pipe = jax.jit(jax.grad(loss_pipe))(stage_params, mbs)
    g_serial = jax.grad(loss_serial)(Ws, mbs)
    np.testing.assert_allclose(np.asarray(g_pipe).reshape(8, D, D),
                               np.asarray(g_serial), rtol=2e-4, atol=2e-4)
    print("PIPELINE_OK")
""")


def test_pipeline_matches_serial():
    # the child compiles in a few seconds; a run past the deadline fails
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=300)
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
