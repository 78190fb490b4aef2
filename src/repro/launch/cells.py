"""Dry-run cell builders: (architecture × input shape × mesh) → lowerable.

For every cell this module produces ``CellProgram``: a jit-able step
function plus ShapeDtypeStruct arguments carrying NamedShardings — lowering
never allocates the (multi-TB) full-size arrays. One builder per arch
family; the launcher and the roofline harness both consume it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, ShapeCell, get_arch
from repro.launch.mesh import data_axes


@dataclasses.dataclass
class CellProgram:
    arch: str
    cell: str
    kind: str
    fn: Callable                     # jit-able step function
    args: Tuple[Any, ...]            # ShapeDtypeStructs with .sharding
    donate: Tuple[int, ...] = ()
    static: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # MODEL_FLOPS (useful work definition) for the roofline's utilisation row
    model_flops: float = 0.0
    note: str = ""


def _sds(shape, dtype, mesh, spec) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _shard_tree(shapes_tree, specs_tree, mesh):
    """Zip a ShapeDtypeStruct tree with a PartitionSpec tree -> sharded SDS."""
    return jax.tree.map(
        lambda s, p: _sds(s.shape, s.dtype, mesh, p), shapes_tree, specs_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _replicated_tree(shapes_tree, mesh):
    return jax.tree.map(
        lambda s: _sds(s.shape, s.dtype, mesh, P()), shapes_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# ===========================================================================
# LM family
# ===========================================================================

def _lm_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh,
             cfg_map=None) -> CellProgram:
    from repro.models import transformer as T
    from repro.models.lm_steps import make_train_step, make_prefill_step
    from repro.optim import adamw_init
    from repro.sharding.lm import lm_sharding, opt_state_specs

    cfg = spec.build()
    if cfg_map is not None:
        cfg = cfg_map(cfg)
    dp = data_axes(mesh)
    sh = lm_sharding(cfg, mesh, dp_axes=dp)
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda: T.init_params(cfg, key))
    params_sds = _shard_tree(params_shape, sh.param_specs, mesh)

    seq = cell.meta["seq_len"]
    batch = cell.meta["global_batch"]
    tok_spec = sh.token_spec(batch)

    n_active = cfg.active_param_count()
    if cell.kind == "train":
        opt_shape = jax.eval_shape(lambda: adamw_init(params_shape))
        opt_sds = _shard_tree(opt_shape, opt_state_specs(sh), mesh)
        tokens = _sds((batch, seq), jnp.int32, mesh, tok_spec)
        targets = _sds((batch, seq), jnp.int32, mesh, tok_spec)
        fn = make_train_step(cfg)
        model_flops = 6.0 * n_active * batch * seq
        return CellProgram(spec.name, cell.name, "train", fn,
                           (params_sds, opt_sds, tokens, targets),
                           donate=(0, 1), model_flops=model_flops)
    if cell.kind == "prefill":
        tokens = _sds((batch, seq), jnp.int32, mesh, tok_spec)
        fn = make_prefill_step(cfg)
        model_flops = 2.0 * n_active * batch * seq
        return CellProgram(spec.name, cell.name, "prefill", fn,
                           (params_sds, tokens), model_flops=model_flops)
    # decode: one new token against a seq_len KV cache
    cache_shape = jax.eval_shape(
        lambda: T.init_cache(cfg, batch, seq))
    cache_sds = _shard_tree(cache_shape,
                            sh.cache_spec(cfg, batch, T.cache_len(cfg, seq)),
                            mesh)
    token = _sds((batch, 1), jnp.int32, mesh, tok_spec)
    fn = lambda p, c, t: T.decode_step(cfg, p, c, t)
    model_flops = 2.0 * n_active * batch * 1
    return CellProgram(spec.name, cell.name, "decode", fn,
                       (params_sds, cache_sds, token), donate=(1,),
                       model_flops=model_flops)


# ===========================================================================
# GNN family
# ===========================================================================

def _gnn_param_flops(arch: str, cfg, meta) -> float:
    from repro.launch.flops import gnn_model_flops
    return gnn_model_flops(arch, cfg, meta)


def _gnn_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    from repro.models import gnn as G
    from repro.models.gnn_steps import FORWARD, make_gnn_train_step
    from repro.optim import adamw_init
    from repro.sharding.gnn import gnn_sharding

    cfg = spec.build()
    meta = dict(cell.meta)
    if spec.name != "dimenet":
        meta["n_triplets"] = 0
    dp = data_axes(mesh)
    sh = gnn_sharding(mesh, meta, dp_axes=dp)

    shapes = G.GraphShapes(n_nodes=meta["n_nodes"], n_edges=meta["n_edges"],
                           d_feat=meta["d_feat"],
                           n_triplets=meta.get("n_triplets", 0),
                           n_graphs=meta.get("n_graphs", 1))
    batch_shape = G.batch_spec(shapes)
    batch_sds = {k: _sds(v.shape, v.dtype, mesh, sh.batch_specs[k])
                 for k, v in batch_shape.items()}

    _, init, fwd, _ = FORWARD[spec.name]
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda: init(cfg, key, meta["d_feat"]))
    params_sds = _replicated_tree(params_shape, mesh)
    opt_shape = jax.eval_shape(lambda: adamw_init(params_shape))
    opt_sds = _replicated_tree(opt_shape, mesh)

    fn = make_gnn_train_step(spec.name, cfg, meta.get("n_graphs", 1))
    return CellProgram(spec.name, cell.name, "train", fn,
                       (params_sds, opt_sds, batch_sds), donate=(0, 1),
                       model_flops=_gnn_param_flops(spec.name, cfg, meta))


# ===========================================================================
# Recsys family
# ===========================================================================

def _recsys_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    from repro.models import recsys as R
    from repro.optim import adamw_init
    from repro.sharding.recsys import recsys_sharding

    cfg = spec.build()
    dp = data_axes(mesh)
    kind = {"train": "train", "serve": "serve", "bulk": "bulk",
            "retrieval": "retrieval"}[cell.kind]
    sh = recsys_sharding(cfg, mesh, kind, cell.meta, dp_axes=dp)

    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(lambda: R.init_params(cfg, key))
    params_sds = _shard_tree(params_shape, sh.param_specs, mesh)

    batch = cell.meta.get("batch", 1)
    spec_map = R.batch_spec(cfg, kind, batch,
                            n_candidates=cell.meta.get("n_candidates", 0))
    batch_sds = {k: _sds(v.shape, v.dtype, mesh, sh.batch_specs[k])
                 for k, v in spec_map.items()}

    from repro.launch.flops import recsys_model_flops
    model_flops = recsys_model_flops(cfg, kind, cell.meta)
    if kind == "train":
        opt_shape = jax.eval_shape(lambda: adamw_init(params_shape))
        opt_specs = dict(mu=sh.param_specs, nu=sh.param_specs, step=P())
        opt_sds = _shard_tree(opt_shape, opt_specs, mesh)
        fn = R.make_train_step(cfg)
        return CellProgram(spec.name, cell.name, "train", fn,
                           (params_sds, opt_sds, batch_sds), donate=(0, 1),
                           model_flops=model_flops)
    fn = {"serve": R.make_serve_step, "bulk": R.make_bulk_score_step,
          "retrieval": R.make_retrieval_step}[kind](cfg)
    return CellProgram(spec.name, cell.name, kind, fn,
                       (params_sds, batch_sds), model_flops=model_flops)


# ===========================================================================
# MCE (the paper's own arch)
# ===========================================================================

def _mce_cell(spec: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    from repro.core.engine import EngineConfig
    from repro.core.driver import _sharded_counts

    cfg_arch = spec.build()
    dp = data_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in dp]))
    m = cell.meta
    r, u, xc = m["roots_chunk"], m["u_pad"], m["x_pad"]
    w = u // 32
    ecfg = EngineConfig(dynamic_red=cfg_arch.dynamic_red,
                        backend=cfg_arch.backend, out_cap=0,
                        max_iters=1 << 20)
    sp = P(dp)
    a = _sds((n_shards, r, u, w), jnp.uint32, mesh, sp)
    p0 = _sds((n_shards, r, w), jnp.uint32, mesh, sp)
    xr = _sds((n_shards, r, xc, w), jnp.uint32, mesh, sp)
    xa = _sds((n_shards, r, xc), jnp.bool_, mesh, sp)
    rz = _sds((n_shards, r), jnp.int32, mesh, sp)

    # each counter comes back as int32[n_shards], one entry per shard: the
    # step holds no collective, and the caller folds the shards
    def fn(a_, p_, x_, l_, z_):
        return _sharded_counts(a_, p_, x_, l_, z_, ecfg, mesh, dp)

    # per while-iteration useful work: deg_P popcount rows over (U, W) words
    model_flops = float(n_shards * r * u * w)
    return CellProgram(spec.name, cell.name, "mce", fn, (a, p0, xr, xa, rz),
                       model_flops=model_flops,
                       note="flops counted per DFS iteration (while_loop "
                            "body), not per full enumeration")


# ===========================================================================
# Dispatcher
# ===========================================================================

def build_cell(arch: str, cell_name: str, mesh: Mesh,
               cfg_map=None) -> CellProgram:
    """cfg_map (LM family only): transform the model config before building
    — the dry-run's roofline calibration lowers 1-/2-layer unrolled variants
    with it (see launch/dryrun.py --calibrated)."""
    spec = get_arch(arch)
    cfg = spec.build()
    cells = {c.name: c for c in spec.shapes(cfg)}
    cell = cells[cell_name]
    if cell.skip_reason:
        raise ValueError(f"cell {arch}/{cell_name} is skipped: "
                         f"{cell.skip_reason}")
    if spec.family == "lm":
        return _lm_cell(spec, cell, mesh, cfg_map=cfg_map)
    builder = {"gnn": _gnn_cell, "recsys": _recsys_cell,
               "mce": _mce_cell}[spec.family]
    return builder(spec, cell, mesh)


def input_specs(arch: str, cell_name: str, mesh: Mesh):
    """ShapeDtypeStruct stand-ins (with shardings) for every input of the
    cell's step function — the no-allocation dry-run contract."""
    return build_cell(arch, cell_name, mesh).args


def all_cells():
    """Yield (arch, cell_name, skip_reason|None) over the assignment matrix."""
    from repro.configs import list_archs
    for arch in list_archs():
        spec = get_arch(arch)
        cfg = spec.build()
        for cell in spec.shapes(cfg):
            yield arch, cell.name, cell.skip_reason
