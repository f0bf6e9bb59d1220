"""Per-architecture smoke tests (deliverable f): a REDUCED config of the
same family runs one forward/train step on CPU — output shapes + no NaNs.
The FULL configs are exercised via the dry-run only."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.data.pipelines import gnn_molecule_batch
from repro.launch import steps
from repro.models import gnn, recsys, transformer
from repro.models.common import Shardings
from repro.optim import adamw_init

SH = Shardings(mesh=None)


def _reduced_lm(cfg: transformer.LMConfig) -> transformer.LMConfig:
    return dataclasses.replace(
        cfg, n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab=128, dtype=jnp.float32, attn_chunk=16,
        n_experts=4 if cfg.moe else 0, top_k=min(cfg.top_k, 2),
        gather_fsdp_in_body=False, seq_shard_activations=False)


def _reduced_gnn(cfg: gnn.GNNConfig) -> gnn.GNNConfig:
    return dataclasses.replace(cfg, n_layers=2, d_hidden=16, d_feat=8,
                               n_out=2, n_classes=5, sharded=False)


def _reduced_recsys(cfg: recsys.RecsysConfig) -> recsys.RecsysConfig:
    return dataclasses.replace(cfg, n_sparse=6, rows_per_field=100,
                               mlp_dims=(32, 16))


def _finite(tree) -> bool:
    return all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("arch_id", list_archs())
def test_arch_smoke_train_step(arch_id):
    spec = get_arch(arch_id)
    key = jax.random.PRNGKey(0)
    if spec.family == "lm":
        cfg = _reduced_lm(spec.model_cfg)
        params = transformer.init_params(cfg, key)
        step = steps.lm_train_step(cfg, SH, n_micro=2)
        tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab)
        p2, o2, metrics = step(params, adamw_init(params), tokens)
        assert np.isfinite(float(metrics["loss"]))
        assert _finite(p2)
        # shapes preserved
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(p2)):
            assert a.shape == b.shape
    elif spec.family == "gnn":
        cfg = _reduced_gnn(spec.model_cfg)
        params = gnn.init_params(cfg, key)
        batch = {k: jnp.asarray(v) for k, v in
                 gnn_molecule_batch(4, 10, 16, cfg.d_feat, seed=1).items()}
        batch["labels"] = batch["labels"] % cfg.n_classes
        batch["target"] = batch["target"][:, :1].repeat(cfg.n_out, 1)
        step = steps.gnn_train_step(cfg, SH)
        p2, o2, metrics = step(params, adamw_init(params), batch)
        assert np.isfinite(float(metrics["loss"]))
        assert _finite(p2)
    else:
        cfg = _reduced_recsys(spec.model_cfg)
        params = recsys.init_params(cfg, key)
        rng = np.random.default_rng(0)
        batch = {
            "sparse_ids": jnp.asarray(rng.integers(
                0, cfg.rows_per_field,
                (8, cfg.n_sparse, cfg.hots_per_field)).astype(np.int32)),
            "dense": jnp.asarray(rng.normal(
                size=(8, cfg.n_dense)).astype(np.float32)),
            "labels": jnp.asarray(rng.integers(0, 2, 8).astype(np.int32)),
        }
        step = steps.recsys_train_step(cfg, SH)
        p2, o2, metrics = step(params, adamw_init(params), batch)
        assert np.isfinite(float(metrics["loss"]))
        assert _finite(p2)


@pytest.mark.parametrize("arch_id", [a for a in list_archs()
                                     if get_arch(a).family == "lm"])
def test_lm_smoke_prefill_decode(arch_id):
    spec = get_arch(arch_id)
    cfg = _reduced_lm(spec.model_cfg)
    params = transformer.init_params(cfg, jax.random.PRNGKey(1))
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0,
                              cfg.vocab)
    logits, cache = transformer.prefill(cfg, SH, params, toks)
    assert logits.shape == (2, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    cache = {"k": jnp.pad(cache["k"], ((0, 0), (0, 0), (0, 4),
                                       (0, 0), (0, 0))),
             "v": jnp.pad(cache["v"], ((0, 0), (0, 0), (0, 4),
                                       (0, 0), (0, 0))),
             "len": cache["len"]}
    logits2, cache = transformer.decode_step(
        cfg, SH, params, cache, toks[:, 0])
    assert logits2.shape == (2, cfg.vocab_padded)
    assert np.isfinite(np.asarray(logits2, np.float32)).all()
    assert int(cache["len"]) == 13


def test_all_cells_build_on_tiny_mesh():
    """Every (arch x shape) cell must assemble (structs + shardings) on
    a 1x1 mesh without touching device memory."""
    from repro.launch.cells import build_cell
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh((1, 1))
    for arch_id in list_archs():
        for cell in get_arch(arch_id).shapes:
            b = build_cell(arch_id, cell.name, mesh)
            assert b.model_flops > 0
            leaves = jax.tree_util.tree_leaves(b.args)
            assert all(isinstance(x, jax.ShapeDtypeStruct)
                       for x in leaves)
