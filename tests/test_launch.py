"""Launch-layer tests: HLO collective parser, spec rules, cell builders,
roofline model-flops sanity, e2e reduced training driver."""
import numpy as np
import pytest

from repro.configs import base as cfgbase
from repro.launch import steps


def test_collective_stats_parser():
    from repro.launch import dryrun

    hlo = """
HloModule test

%fused (a: f32[128,256]) -> f32[128,256] {
  %a = f32[128,256] parameter(0)
  ROOT %r = f32[128,256] add(%a, %a)
}

while_body_1 {
  %p = f32[64,64] parameter(0)
  %ar2 = f32[64,64] all-reduce(%p), replica_groups={}
  ROOT %t = f32[64,64] add(%ar2, %ar2)
}

ENTRY main {
  %x = f32[1024,1024] parameter(0)
  %y = bf16[512] parameter(1)
  %ag = bf16[8192] all-gather(%y), dimensions={0}
  %ar = f32[1024,1024] all-reduce(%x), to_apply=%sum
  ROOT %out = f32[1024,1024] add(%ar, %ar)
}
"""
    stats = dryrun.collective_stats(hlo)
    assert stats["total_bytes"]["all-gather"] == 512 * 2
    assert stats["total_bytes"]["all-reduce"] == 1024 * 1024 * 4 + 64 * 64 * 4
    assert stats["while_body_bytes"]["all-reduce"] == 64 * 64 * 4


def test_type_bytes():
    from repro.launch.dryrun import _type_bytes

    assert _type_bytes("f32[128,256]") == 128 * 256 * 4
    assert _type_bytes("bf16[10]") == 20
    assert _type_bytes("(f32[4], s32[2])") == 16 + 8
    assert _type_bytes("pred[]") == 1


def test_divisibility_guard_drops_axes():
    """tree_spec must replicate leaves whose dims don't divide the mesh."""
    import subprocess
    import sys
    import textwrap
    import os

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch import shardings, mesh as mesh_mod
        mesh = mesh_mod.make_mesh_like((2, 4), ("data", "model"))
        tree = {"ok": jnp.zeros((8, 4)), "odd": jnp.zeros((7, 4)),
                "scalar": jnp.zeros(())}
        out = shardings.tree_spec(tree, lambda p, m: P("data", None), mesh)
        assert out["ok"].spec == P("data", None), out["ok"].spec
        assert out["odd"].spec == P(None, None), out["odd"].spec
        print("guard-ok")
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
        timeout=300,
    )
    assert "guard-ok" in r.stdout, r.stderr[-2000:]


def test_shard_map_check_vma_on_host_mesh():
    """jax.shard_map runs on a host mesh with the replication check on
    and off (the sharded walk's all_gather frontier needs it off)."""
    import subprocess
    import sys
    import textwrap
    import os

    code = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch import mesh as mesh_mod

        mesh = mesh_mod.host_mesh(4)
        f = jax.shard_map(
            lambda x: jax.lax.psum(x, "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=True)
        out = jax.jit(f)(jnp.arange(8, dtype=jnp.float32))
        assert float(out.sum()) == 28.0, out
        # check_vma=False compiles too (device-varying out under P())
        g = jax.shard_map(
            lambda x: jax.lax.all_gather(x, "data", tiled=True),
            mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
        out2 = jax.jit(g)(jnp.arange(8, dtype=jnp.float32))
        assert out2.shape == (8,) and float(out2[5]) == 5.0
        print("shard-map-ok")
        """
    )
    r = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"},
        timeout=300,
    )
    assert "shard-map-ok" in r.stdout, r.stderr[-2000:]


def test_host_mesh_rejects_oversubscription():
    from repro.launch import mesh as mesh_mod

    import jax

    with pytest.raises(ValueError, match="host_mesh"):
        mesh_mod.host_mesh(len(jax.devices()) + 1)


@pytest.mark.parametrize("arch", ["gcn-cora", "h2o-danube-1.8b", "two-tower-retrieval"])
def test_build_cell_full_specs_are_abstract(arch):
    """Full-scale cells must be pure ShapeDtypeStructs (no allocation)."""
    import jax

    fam = cfgbase.get(arch).family
    shape = {"lm": "train_4k", "gnn": "full_graph_sm", "recsys": "train_batch"}[fam]
    cell = steps.build_cell(arch, shape, reduced=False)
    for leaf in jax.tree.leaves(
        cell.args, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct)
    ):
        assert isinstance(leaf, jax.ShapeDtypeStruct) or not hasattr(leaf, "shape"), type(leaf)


def test_model_flops_matches_small_scale_hlo():
    """Closed-form MODEL_FLOPS validated against a compiled small model."""
    import dataclasses
    import functools
    import jax
    import jax.numpy as jnp
    from repro.models.gnn import graphcast
    from repro.train import loop, optimizer as opt

    cfg = graphcast.GraphCastConfig(n_layers=3, d_hidden=32, n_vars=8)
    n, e = 256, 1024
    rng = np.random.default_rng(0)
    g = {
        "node_feat": jnp.asarray(rng.standard_normal((n, 8)), jnp.float32),
        "edge_src": jnp.asarray(rng.integers(0, n, e), jnp.int32),
        "edge_dst": jnp.asarray(rng.integers(0, n, e), jnp.int32),
        "positions": jnp.asarray(rng.standard_normal((n, 3)), jnp.float32),
        "labels": jnp.asarray(rng.standard_normal((n, 8)), jnp.float32),
    }
    params = graphcast.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = opt.OptimizerConfig()
    state = loop.init_state(params, ocfg)
    step = loop.make_train_step(lambda p, b: graphcast.loss_fn(p, b, cfg), ocfg)
    c = jax.jit(step).lower(state, g).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):  # jax < 0.5 returns one dict per device
        ca = ca[0]
    hlo = ca["flops"]
    d, nv = cfg.d_hidden, cfg.n_vars
    fwd = 2 * n * (nv * d + d * d) * 2 + cfg.n_layers * (
        2 * e * (3 * d * d + d * d) + 2 * n * (2 * d * d + d * d)
    )
    assert 0.5 < hlo / (3 * fwd) < 2.0, hlo / (3 * fwd)


def test_train_driver_e2e(tmp_path):
    """launch/train.py reduces loss and restarts from checkpoints."""
    from repro.launch import train as train_mod

    ck = str(tmp_path / "ck")
    losses = train_mod.main(
        ["--arch", "gcn-cora", "--steps", "25", "--ckpt-dir", ck,
         "--ckpt-every", "10", "--log-every", "10"]
    )
    assert losses[-1] < losses[0]
    # resume path
    losses2 = train_mod.main(
        ["--arch", "gcn-cora", "--steps", "5", "--ckpt-dir", ck, "--resume"]
    )
    assert losses2[0] <= losses[0]


def test_all_cell_variants_buildable():
    """Every non-skipped cell × its roofline variants constructs."""
    from repro.launch import dryrun

    for arch, shape, skip in cfgbase.all_cells():
        if skip:
            continue
        for v in dryrun.variants_for(arch, shape):
            if v.startswith("opt"):
                cell = steps.build_opt_cell(arch, variant=v)
            else:
                cell = steps.build_cell(arch, shape, variant=v)
            assert cell.step_fn is not None
