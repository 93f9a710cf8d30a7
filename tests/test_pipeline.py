"""Pipeline parallelism (GPipe over the pod axis): schedule, exactness,
and a real 4-device shard_map run (subprocess so the device count can be
forced before jax initializes)."""
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.pipeline import (gpipe_schedule,
                                        make_pipelined_stack, split_stages)

# the checkout under test: subprocess programs import its src/
ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_gpipe_schedule_shape_and_bubble():
    sched = gpipe_schedule(n_micro=4, n_stages=2)
    assert sched == [[0, -1], [1, 0], [2, 1], [3, 2], [-1, 3]]
    # bubble fraction = (S-1)/(M+S-1)
    bubbles = sum(1 for tick in sched for m in tick if m < 0)
    assert bubbles == 2 * (2 - 1)


def test_split_stages_partitions_layers():
    ws = jnp.arange(24.0).reshape(6, 2, 2)
    st = split_stages(ws, 3)
    assert st.shape == (3, 2, 2, 2)
    np.testing.assert_array_equal(np.asarray(st[0]), np.asarray(ws[:2]))


def test_sequential_emulation_exact():
    L, D = 8, 16
    ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.1

    def layer_fn(lp, x):
        return x + jnp.tanh(x @ lp)

    x_micro = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 6, D))

    def ref_run(ws, xm):
        def body(x, w):
            return layer_fn(w, x), None
        return jnp.stack([jax.lax.scan(body, xm[m], ws)[0]
                          for m in range(xm.shape[0])])

    ref = ref_run(ws, x_micro)
    for n_stages in (1, 2, 4):
        run = make_pipelined_stack(None, layer_fn, n_stages=n_stages,
                                   mesh=None)
        np.testing.assert_allclose(np.asarray(run(ws, x_micro)),
                                   np.asarray(ref), rtol=1e-6, atol=1e-6)


def test_shard_map_pipeline_on_four_devices():
    """Runs in a subprocess with 4 forced host devices (ppermute path)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from repro.distributed.pipeline import make_pipelined_stack
        L, D = 8, 16
        ws = jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.1
        def layer_fn(lp, x):
            return x + jnp.tanh(x @ lp)
        xm = jax.random.normal(jax.random.PRNGKey(1), (4, 2, 6, D))
        def body(x, w): return layer_fn(w, x), None
        ref = jnp.stack([jax.lax.scan(body, xm[m], ws)[0]
                         for m in range(4)])
        mesh = jax.make_mesh((4,), ("pod",), devices=jax.devices()[:4])
        run = make_pipelined_stack(None, layer_fn, n_stages=4, mesh=mesh)
        with mesh:
            out = jax.jit(run)(ws, xm)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-5, err
        print("PIPELINE_OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd=ROOT, timeout=300)
    assert "PIPELINE_OK" in r.stdout, (r.stdout, r.stderr[-1500:])
