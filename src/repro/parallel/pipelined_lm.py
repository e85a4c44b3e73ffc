"""Pipelined LM training step: GPipe over the pod axis for uniform-stack
dense architectures.

FSDP over (pod, data) all-gathers every weight across the DCN between pods
each layer; pipelining instead keeps weights POD-LOCAL (the layer stack's
leading dim is sharded over "pod") and sends only microbatch activations at
stage boundaries — cross-pod traffic drops from O(params) to
O(microbatches x mb x S x d) per step, plus ONE scalar (the loss).

shard_map is manual over {"pod"} only; "data"/"model" stay auto-sharded by
GSPMD inside the stage (FSDP+TP within a pod, PP across pods).  The loss is
computed inside the manual region on the last stage and psum-masked — no
activation broadcast across pods at all.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import blocks, lm
from repro.models.config import ArchConfig
from repro.models.params import shard_act, sharding_rules

from .pipeline import pipeline_stages


def supports_pipeline(cfg: ArchConfig) -> bool:
    segs = cfg.resolved_segments()
    return len(segs) == 1 and segs[0][0] in ("attn_mlp",)


def pipelined_loss_fn(params: Dict[str, Any], cfg: ArchConfig,
                      batch: Dict[str, jax.Array], mesh, rules: Dict,
                      num_microbatches: int = 8):
    """Cross-entropy loss with the layer stack executed as a pod-axis
    pipeline.  params["segments"][0]["layers"] leading dim is sharded P("pod")."""
    assert supports_pipeline(cfg), "pipeline supports uniform dense stacks"
    dt = jnp.dtype(cfg.compute_dtype)
    tokens = batch["tokens"]
    labels = batch["labels"]
    bsz, s = tokens.shape
    m = num_microbatches
    while bsz % m:
        m -= 1
    mb = bsz // m

    with sharding_rules(mesh, rules):
        x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        x = shard_act(x, "dp", None, None)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (mb, s))
        x_mb = x.reshape(m, mb, s, cfg.d_model)
        lab_mb = labels.reshape(m, mb, s)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(dt)

        def stage(layers_local, xin):
            def layer(x_, lp):
                x2, _, _ = lm._block_fwd(cfg, "attn_mlp", lp, x_,
                                         positions, None, 1)
                return x2, None
            body = layer
            if cfg.remat:
                body = jax.checkpoint(
                    layer, policy=jax.checkpoint_policies.nothing_saveable)
            out, _ = jax.lax.scan(body, xin, layers_local)
            return out

        def pod_body(layers_stage, xmb, labmb, norm_p, head_):
            outs, me, stages = pipeline_stages(stage, layers_stage, xmb,
                                               "pod")
            # head + loss on the LAST stage only; psum the masked scalar
            y = blocks.apply_norm(norm_p, cfg, outs.reshape(bsz, s,
                                                            cfg.d_model))
            logits = y @ head_
            logits = lm._mask_pad_vocab(cfg, logits)
            lab = labmb.reshape(bsz, s)
            mx = jnp.max(logits, axis=-1).astype(jnp.float32)
            shifted = logits.astype(jnp.float32) - mx[..., None]
            logz = mx + jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
            ll = jnp.take_along_axis(
                logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
            mask = (lab >= 0).astype(jnp.float32)
            nll = jnp.sum((logz - ll.astype(jnp.float32)) * mask) \
                / jnp.maximum(jnp.sum(mask), 1.0)
            nll = jnp.where(me == stages - 1, nll, 0.0)
            return jax.lax.psum(nll, "pod")

        # manual over "pod" only: "data"/"model" stay auto inside
        nll = jax.shard_map(
            pod_body, mesh=mesh,
            in_specs=(P("pod"), P(None, None, None, None),
                      P(None, None, None), P(), P(None, None)),
            out_specs=P(),
            axis_names={"pod"}, check_vma=False,
        )(params["segments"][0]["layers"], x_mb, lab_mb,
          params["final_norm"], head)
    return nll, {"nll": nll, "aux": jnp.zeros((), jnp.float32)}


def pipeline_param_shardings(mesh, meta_tree, rules: Dict):
    """Like logical_shardings but the layer-stack leading dim goes to the
    pod axis (stage placement) instead of replication."""
    from repro.parallel.rules import logical_shardings
    base = logical_shardings(mesh, meta_tree, rules)

    def restage(path, sh):
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        if "segments" in keys and "layers" in keys:
            spec = list(sh.spec) + [None] * 8
            spec[0] = "pod"
            ndim = len(sh.spec)
            return NamedSharding(mesh, P(*spec[:max(ndim, 1)]))
        return sh

    return jax.tree_util.tree_map_with_path(restage, base)
