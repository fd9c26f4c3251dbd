"""Training-side fault tolerance: Supervisor, StragglerMonitor, elastic_remesh.

The serving-side recovery matrix lives in tests/test_recovery.py; this file
covers the training loop's pieces from training/fault_tolerance.py:

  * Supervisor restart-and-replay with a sketch table in the step state --
    an injected step failure restores the latest checkpoint and replays,
    and because the data order is keyed by step number the final state is
    bit-identical to an uninterrupted run;
  * restart budget: exceeding ``max_restarts`` re-raises instead of
    looping forever, and ``restart_backoff`` actually sleeps between
    restarts (exponentially);
  * StragglerMonitor flags an injected slow host and un-flags it once its
    EWMA recovers;
  * elastic_remesh re-lays live sharded state onto a smaller/larger mesh
    with values intact (multi-device leg runs in a subprocess on a forced
    CPU mesh).
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.core import sketch as sk
from repro.kernels.ops import KernelSketch
from repro.launch.mesh import make_mesh
from repro.streams import zipf_hh_workload
from repro.training.fault_tolerance import (
    StragglerMonitor,
    Supervisor,
    elastic_remesh,
)

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")
_DEVICES = int(os.environ.get("REPRO_TEST_DEVICES", "8"))


def _run(code: str, devices: int = _DEVICES) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def _sketch_step_setup():
    """A step loop whose state is a KernelSketch table: step i folds block i.

    Data order is keyed by the step number, so replay after a restore
    consumes identical blocks -- the exactly-once contract under test.
    The kernel fold donates its input buffer, so each run gets a FRESH
    init via the returned factory (a shared init array would be deleted
    by the first run's first step).
    """
    import jax.numpy as jnp

    stream = zipf_hh_workload(n_src=100, n_tgt=200, n_edges=800,
                              n_occurrences=4_000, seed=1).stream
    spec = sk.mod_sketch_spec(stream.schema, [(0,), (1,)], (32, 32), 4)
    ks = KernelSketch(spec, jax.random.PRNGKey(0), block_b=64)
    init_table = np.asarray(ks.table)
    blocks = [(stream.items[s:s + 50], stream.freqs[s:s + 50])
              for s in range(0, stream.items.shape[0], 50)]

    def step_fn(i, state):
        it, fr = blocks[i % len(blocks)]
        ks.table = jnp.asarray(state["table"])
        ks.update(it, fr)
        return {"table": ks.table, "step_no": np.asarray(i + 1)}

    def make_init():
        return {"table": jnp.array(init_table), "step_no": np.asarray(0)}

    return step_fn, make_init, len(blocks)


def test_supervisor_restart_and_replay_bitwise(tmp_path):
    step_fn, make_init, n = _sketch_step_setup()
    _, ref_state = Supervisor(str(tmp_path / "ref"), save_every=3,
                              ).run(make_init(), step_fn, 0, n)

    boom = {"armed": True}

    def flaky(i, state):
        if i == 5 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected device loss")
        return step_fn(i, state)

    sup = Supervisor(str(tmp_path / "ckpt"), save_every=3)
    step, state = sup.run(make_init(), flaky, 0, n)
    assert step == n and sup.restarts == 1
    assert np.array_equal(np.asarray(state["table"]),
                          np.asarray(ref_state["table"]))
    assert int(state["step_no"]) == n


def test_supervisor_max_restarts_exceeded(tmp_path):
    step_fn, make_init, n = _sketch_step_setup()

    def always_fails(i, state):
        raise RuntimeError("persistent failure")

    sup = Supervisor(str(tmp_path), save_every=3, max_restarts=2)
    with pytest.raises(RuntimeError, match="max_restarts"):
        sup.run(make_init(), always_fails, 0, n)
    assert sup.restarts == 3                 # 2 allowed + the fatal one


def test_supervisor_restart_backoff_sleeps(tmp_path):
    step_fn, make_init, n = _sketch_step_setup()
    boom = {"left": 2}

    def flaky(i, state):
        if boom["left"]:
            boom["left"] -= 1
            raise RuntimeError("injected")
        return step_fn(i, state)

    sup = Supervisor(str(tmp_path), save_every=100, max_restarts=3,
                     restart_backoff=0.05)
    t0 = time.perf_counter()
    sup.run(make_init(), flaky, 0, 3)
    # backoff 0.05 * (1 + 2) = 0.15s floor across the two restarts
    assert time.perf_counter() - t0 >= 0.15
    assert sup.restarts == 2


def test_straggler_monitor_flags_and_recovers():
    mon = StragglerMonitor(threshold=2.0, ewma=0.5)
    # warm: four hosts at ~10ms
    for step in range(3):
        mon.record(step, {h: 0.010 for h in range(4)})
    assert mon.reports[-1].stragglers == []
    # host 2 degrades to 100ms: EWMA crosses 2x median within a few steps
    for step in range(3, 8):
        times = {h: 0.010 for h in range(4)}
        times[2] = 0.100
        rep = mon.record(step, times)
    assert rep.stragglers == [2]
    # and heals once the host speeds back up
    for step in range(8, 20):
        rep = mon.record(step, {h: 0.010 for h in range(4)})
    assert rep.stragglers == []


def test_elastic_remesh_single_device_roundtrip():
    # 1->1 remesh is the degenerate leg runnable on any host: values and
    # structure survive the device_put relayout
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import PartitionSpec as P

    state = {"table": jax.numpy.arange(64, dtype=jax.numpy.int32).reshape(8, 8),
             "count": jax.numpy.asarray(7)}
    out = elastic_remesh(state, mesh, lambda x: P())
    assert np.array_equal(np.asarray(out["table"]),
                          np.asarray(state["table"]))
    assert int(out["count"]) == 7


def test_elastic_remesh_multi_device_shrink_grow():
    print(_run("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.training.fault_tolerance import elastic_remesh

        assert jax.device_count() >= 8, jax.device_count()
        mesh8 = make_mesh((8,), ("data",))
        mesh4 = make_mesh((4,), ("data",))   # lost half the fleet
        x = jax.device_put(
            jax.numpy.arange(1024, dtype=jax.numpy.float32).reshape(8, 128),
            NamedSharding(mesh8, P("data")))
        state = {"table": x, "step": jax.numpy.asarray(11)}

        down = elastic_remesh(state, mesh4,
                              lambda v: P("data") if v.ndim == 2 else P())
        assert down["table"].sharding.mesh == mesh4
        assert np.array_equal(np.asarray(down["table"]), np.asarray(x))
        assert int(down["step"]) == 11

        up = elastic_remesh(down, mesh8,
                            lambda v: P("data") if v.ndim == 2 else P())
        assert up["table"].sharding.mesh == mesh8
        assert np.array_equal(np.asarray(up["table"]), np.asarray(x))
        print("elastic remesh 8->4->8 values intact")
    """))
