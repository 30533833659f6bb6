"""Multi-device behaviour (subprocess with 4 host devices): real sharded
training, elastic shrink with checkpoint reshard, pilot over a device set,
and the compressed cross-pod psum on an actual pod axis."""
import subprocess
import sys
import textwrap

import pytest

REPO = "/root/repo"


def run_prog(prog: str, timeout: int = 540) -> str:
    full = ("import os\n"
            "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'\n"
            "import sys; sys.path.insert(0, 'src')\n" + textwrap.dedent(prog))
    r = subprocess.run([sys.executable, "-c", full], capture_output=True,
                       text=True, cwd=REPO, timeout=timeout)
    assert "OK" in r.stdout, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_sharded_training_matches_single_device():
    """The same seed on a (2,2) mesh and a (1,1) mesh gives the same loss
    trajectory — sharding must not change the math."""
    run_prog("""
    import jax, numpy as np
    from repro import configs
    from repro.train.trainer import Trainer

    cfg = configs.get_smoke("internlm2-1.8b")
    losses = {}
    for shape in [(2, 2), (1, 1)]:
        from repro import compat
        mesh = compat.make_mesh(shape, ("data", "model"))
        tr = Trainer(cfg, mesh, global_batch=4, seq=16, seed=5)
        losses[shape] = [h["loss"] for h in tr.run(4, log_every=0)]
    np.testing.assert_allclose(losses[(2, 2)], losses[(1, 1)], rtol=2e-2)
    print("OK", losses[(1, 1)])
    """)


def test_elastic_shrink_reshard_restore():
    """Train on 4 devices, checkpoint, 'lose' half the pilot, restore onto
    the surviving 2-device mesh and keep training — the checkpoint layout
    reshards transparently."""
    run_prog("""
    import jax, numpy as np, tempfile
    from repro import configs
    from repro.core import PilotManager, PilotDescription, ResourceManager
    from repro.train.trainer import Trainer

    cfg = configs.get_smoke("yi-6b")
    d = tempfile.mkdtemp()
    pm = PilotManager(ResourceManager())
    pilot = pm.submit(PilotDescription(n_chips=4, tp=2))
    tr = Trainer(cfg, pilot.mesh(), global_batch=4, seq=16, ckpt_dir=d,
                 ckpt_every=3, seed=7)
    tr.run(6, log_every=0)

    # node failure takes two devices; pilot shrinks; new mesh is (1, 2)
    pilot.fail_device(pilot.devices[-1])
    pilot.fail_device(pilot.devices[-1])
    assert len(pilot.devices) == 2
    mesh2 = pilot.mesh(tp=2)
    tr2 = Trainer(cfg, mesh2, global_batch=4, seq=16, ckpt_dir=d, seed=7)
    step = tr2.restore()
    assert step == 6, step
    hist = tr2.run(8, log_every=0)
    assert [h["step"] for h in hist] == [6, 7]

    # reference: uninterrupted 1-device run, same seed
    from repro import compat
    mesh1 = compat.make_mesh((1, 1), ("data", "model"))
    tr3 = Trainer(cfg, mesh1, global_batch=4, seq=16, seed=7)
    ref = {h["step"]: h["loss"] for h in tr3.run(8, log_every=0)}
    for h in hist:
        np.testing.assert_allclose(h["loss"], ref[h["step"]], rtol=2e-2)
    pm.shutdown()
    print("OK")
    """)


def test_pilot_gang_mesh_multidevice():
    """A gang CU sees a mesh spanning its assigned devices."""
    run_prog("""
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import (PilotManager, PilotDescription,
                            ComputeUnitDescription, ResourceManager)

    pm = PilotManager(ResourceManager())
    pilot = pm.submit(PilotDescription(n_chips=4, tp=2))

    def hpc(mesh=None):
        assert mesh.size == 4, mesh
        with jax.set_mesh(mesh):
            x = jax.device_put(jnp.arange(16.0).reshape(8, 2),
                               NamedSharding(mesh, P("data", "model")))
            return float(jax.jit(lambda v: (v * v).sum())(x))

    cu = pilot.submit(ComputeUnitDescription(fn=hpc, gang=True, n_chips=4))
    assert cu.wait(120) == float(sum(i * i for i in range(16)))
    # two 2-chip CUs can run side by side after the gang finishes
    cus = [pilot.submit(ComputeUnitDescription(
        fn=lambda mesh=None: mesh.size, gang=True, n_chips=2))
        for _ in range(2)]
    assert [c.wait(120) for c in cus] == [2, 2]
    pm.shutdown()
    print("OK")
    """)


def test_compressed_psum_on_pod_axis():
    """int8 EF psum over a real 4-way axis ~= exact f32 psum."""
    run_prog("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim import compression

    from repro import compat
    mesh = compat.make_mesh((4,), ("pod",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(4, 256)).astype(np.float32))
    res = jnp.zeros_like(x)

    def f(xs, rs):
        out, nr = compression.compressed_psum(xs, rs, "pod")
        return out, nr

    g = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P("pod"), P("pod")),
                                 out_specs=(P("pod"), P("pod")),
                                 check_vma=False))
    out, nr = g(x, res)
    exact = jnp.broadcast_to(x.sum(axis=0, keepdims=True), x.shape)
    rel = float(jnp.max(jnp.abs(out - exact)) / jnp.max(jnp.abs(exact)))
    assert rel < 0.05, rel
    print("OK", rel)
    """)
