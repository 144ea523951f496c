"""Sharding rules + the sharded multi-client uplink dispatch.

``shard_transmit_batch`` scales ``transport.transmit_batch`` across a device
mesh: the client dim is sharded over the data axes, each shard runs the fused
batched PHY on its cohort with *globally indexed* fold_in keys, so the result
is bit-identical to the unsharded batch regardless of mesh shape.

Param/input/cache PartitionSpecs per architecture family.

Rules are path-pattern based and *divisibility-checked*: if a dim is not
divisible by the product of requested mesh axes, the axis is dropped for
that dim (replication) — guaranteeing every (arch x shape x mesh) combo
lowers. Strategy:

* tensor parallelism over ``model`` on head/FFN/expert-inner dims;
* FSDP (param + grad sharding) over the data axes on the other matmul dim,
  enabled per-arch via ``fsdp`` (required for kimi-k2's 2 TB of weights;
  disabled for the paper-faithful per-client uplink step, which needs
  params replicated over the client axes);
* MoE expert dim over the data axes (expert parallelism);
* batch dims of inputs/caches over the data axes; KV-cache head dim over
  ``model`` when divisible, else the sequence dim.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes

Axis = Any  # str | tuple[str, ...] | None

def _over_clients(body, mesh, axes, operands, *, check_vma=True):
    """``body(offset, *local_operands) -> (x_hat, stats)`` with every
    operand's leading (client) dim sharded over ``axes``; ``offset`` is the
    global index of the shard's first client.

    The ``shard_map`` is jitted whole (an eager one dispatches its body op
    by op) and returns per-client, client-sharded values only.
    """
    n_shards = math.prod(mesh.shape[a] for a in axes)
    num_clients = operands[0].shape[0]
    if num_clients % n_shards != 0:
        raise ValueError(
            f"{num_clients} clients do not shard evenly over {n_shards} devices"
        )
    local_clients = num_clients // n_shards
    ax_spec = axes if len(axes) > 1 else axes[0]

    def local(*ops):
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return body(idx * local_clients, *ops)

    in_specs = (P(ax_spec, None),) + (P(ax_spec),) * (len(operands) - 1)
    return jax.jit(jax.shard_map(
        local, mesh=mesh, check_vma=check_vma, in_specs=in_specs,
        out_specs=(P(ax_spec, None), P(ax_spec)),
    ))(*operands)


def shard_transmit_batch(x, key, cfg, mesh, *, axis_names=None, snr_db=None):
    """Run the batched uplink with the client dim sharded over ``axis_names``.

    Args:
      x: ``(num_clients, N)`` payload matrix; ``num_clients`` must divide
        evenly over the product of the mesh's ``axis_names`` sizes.
      key: base PRNG key. Client ``i`` (global index) uses
        ``fold_in(key, i)`` — each shard offsets by its cohort start, so
        sharded == unsharded bit-for-bit.
      cfg: ``transport.TransportConfig``.
      mesh: a ``jax.sharding.Mesh``; ``axis_names`` defaults to every axis
        except ``model`` (see :func:`repro.launch.mesh.data_axes`).
      snr_db: optional per-client ``(num_clients,)`` SNR array (sharded along
        with the clients) or scalar.

    Returns:
      ``(x_hat, stats)`` exactly as ``transport.transmit_batch`` — global
      ``(num_clients, N)`` outputs and per-client ``TxStats``.
    """
    from repro.core import transport as transport_lib
    from repro.kernels.ops import default_interpret

    axes = tuple(axis_names) if axis_names is not None else data_axes(mesh)
    if not axes:  # e.g. a pure tensor-parallel mesh: nothing to shard over
        return transport_lib.transmit_batch(x, key, cfg, snr_db=snr_db)
    snr_vec = transport_lib._resolve_batch_snr(cfg, x.shape[0], snr_db)
    operands = (x,) if snr_vec is None else (x, snr_vec)

    def body(offset, xl, *sl):
        return transport_lib.transmit_batch(
            xl, key, cfg, snr_db=sl[0] if sl else None, client_offset=offset)

    # The Pallas interpreter (the kernel off-TPU) indexes the per-client
    # scalar refs with the unvarying grid coordinate, which the varying-axes
    # check refuses; the compiled kernel passes it.
    interpreted = cfg.use_kernel and default_interpret()
    return _over_clients(body, mesh, axes, operands,
                         check_vma=not interpreted)


def shard_transmit_batch_adaptive(x, key, cfgs, mode_idx, mesh, *,
                                  axis_names=None, snr_db=None):
    """Sharded mixed-mode uplink: the client dim over the mesh's data axes.

    Each shard runs ``transport.transmit_batch_adaptive`` on its cohort with
    globally indexed fold_in keys; ``mode_idx`` (and a per-client ``snr_db``)
    shard along the clients, so the result — received payloads and per-client
    ``TxStats`` including ``mode_idx`` — is bit-identical, whatever the mesh
    shape, to the unsharded ``dispatch="select"`` call.

    Inside the ``shard_map`` body the mode vector is traced, so the per-shard
    dispatch is necessarily ``"select"`` (every shard pays every mode's
    FLOPs for its cohort). The Pallas grid cannot lower in the traced select
    body, so a table with ``use_kernel`` rows is refused: clear them with
    ``transport.clear_kernel_rows`` (the jnp rows draw a different, equally
    valid, channel realization). The single-host bucketed dispatch is the
    kernel path when the cohort fits one process.
    """
    from repro.core import transport as transport_lib

    if any(c.use_kernel for c in cfgs):
        raise ValueError(
            "shard_transmit_batch_adaptive runs the select dispatch, which "
            "has no kernel rows; pass transport.clear_kernel_rows(cfgs)")
    axes = tuple(axis_names) if axis_names is not None else data_axes(mesh)
    if not axes:
        return transport_lib.transmit_batch_adaptive(
            x, key, cfgs, mode_idx, snr_db=snr_db)
    snr_vec = transport_lib._resolve_batch_snr(cfgs[0], x.shape[0], snr_db)
    mode_arr = jnp.asarray(mode_idx, jnp.int32)
    operands = ((x, mode_arr) if snr_vec is None
                else (x, mode_arr, snr_vec))

    def body(offset, xl, ml, *sl):
        return transport_lib.transmit_batch_adaptive(
            xl, key, cfgs, ml, snr_db=sl[0] if sl else None,
            client_offset=offset, dispatch="select")

    return _over_clients(body, mesh, axes, operands)


import re


def normalize_path(keystr: str) -> str:
    """"['layers']['attn']['wq']" / "['blocks'][0]['wq']" -> "layers/attn/wq"."""
    return "/".join(re.findall(r"[A-Za-z_0-9]+", keystr)).lower()


def leaf_name(path: str) -> str:
    return path.rsplit("/", 1)[-1]


def _fits(shape_dim: int, axes: Axis, mesh) -> bool:
    if axes is None:
        return True
    ax = (axes,) if isinstance(axes, str) else axes
    n = math.prod(mesh.shape[a] for a in ax)
    return shape_dim % n == 0 and shape_dim >= n


def checked_spec(shape, axes_per_dim, mesh) -> P:
    """Drop axes on dims where divisibility fails."""
    out = []
    for dim, axes in zip(shape, axes_per_dim):
        out.append(axes if _fits(dim, axes, mesh) else None)
    return P(*out)


def param_rules(path: str, shape, cfg, mesh, *, fsdp: bool) -> P:
    d = data_axes(mesh)
    F = d if fsdp else None  # FSDP axis group
    low = normalize_path(path)

    def spec(*axes_per_dim):
        return checked_spec(shape, axes_per_dim, mesh)

    # embeddings / heads. NOTE: the embedding table is fully REPLICATED.
    # XLA's PartitionGather cost evaluation hard-crashes (Check failure in
    # ExpandDeviceGroupsWithIota, spmd_partitioner_util.cc:504) for several
    # of our (vocab, d_model) shapes when either operand dim is sharded —
    # measured on yi-6b/chatglm3/deepseek train_4k; qwen2 happened to pass.
    # Replicating costs <= 2.3 GB/device (kimi-k2) and sidesteps the bug;
    # the lm_head projection (a matmul, not a gather) stays tensor-sharded.
    if "pos_embed" in low:
        return spec(None, None)
    if "embed" in low:
        return spec(None, None)
    if "lm_head" in low or "vision_proj" in low:
        return spec(F, "model")
    # MoE
    if "router" in low:
        return spec(*([None] * (len(shape) - 2)), None, None)
    if "shared" in low:  # shared-expert MLP, stacked (L, D, Fs)/(L, Fs, D)
        if leaf_name(low) in ("wi", "wg"):
            return spec(None, F, "model") if len(shape) == 3 else spec(F, "model")
        return spec(None, "model", F) if len(shape) == 3 else spec("model", F)
    if "moe" in low and leaf_name(low) in ("wi", "wg"):
        # (L, E, D, F): experts over data axes (expert parallel), F over model
        return spec(None, d, None, "model") if len(shape) == 4 else spec(d, None, "model")
    if "moe" in low and leaf_name(low) == "wo":
        return spec(None, d, "model", None) if len(shape) == 4 else spec(d, "model", None)
    # attention & dense mlp (stacked (L, in, out) or flat (in, out))
    two = {"wq", "wk", "wv", "wi", "wg", "w_x", "w_gate", "w_r", "w_i",
           "in_proj", "dt_proj"}
    back = {"wo", "w_out", "out_proj"}
    leaf = leaf_name(low)
    for name in two:
        if name == leaf:
            if len(shape) == 3:
                return spec(None, F, "model")
            return spec(F, "model")
    for name in back:
        if name == leaf:
            if len(shape) == 3:
                return spec(None, "model", F)
            return spec("model", F)
    if leaf == "x_proj":  # (L, Di, R+2N): Di is model-sharded upstream
        if len(shape) == 3:
            return spec(None, "model", None)
        return spec("model", None)
    if leaf in ("a_log", "d_skip"):
        if len(shape) == 3:
            return spec(None, "model", None)
        return spec("model", None) if len(shape) == 2 else spec("model")
    if leaf == "conv_w":
        return spec(*([None] * (len(shape) - 1)), "model")
    if leaf in ("bq", "bk", "bv", "bi", "bo", "conv_b", "dt_bias", "lam"):
        if len(shape) == 2:
            return spec(None, "model")
        return spec("model") if _fits(shape[-1], "model", mesh) else P(None)
    # norms, biases, everything else: replicated
    return P(*([None] * len(shape)))


def tree_shardings(tree, cfg, mesh, *, fsdp: bool):
    """NamedSharding pytree for a param(-like) pytree or its ShapeDtype tree."""

    def one(path, leaf):
        pstr = jax.tree_util.keystr(path)
        return NamedSharding(mesh, param_rules(pstr, leaf.shape, cfg, mesh, fsdp=fsdp))

    return jax.tree_util.tree_map_with_path(one, tree)


def spec_tree(shardings):
    return jax.tree_util.tree_map(lambda s: s.spec, shardings)


def batch_specs(cfg, shape_cfg, mesh) -> dict:
    """PartitionSpecs for the input batch dict."""
    d = data_axes(mesh)
    B = shape_cfg.global_batch
    bdim = d if _fits(B, d, mesh) else None
    specs = {"tokens": P(bdim, None)}
    if shape_cfg.kind == "train":
        specs["labels"] = P(bdim, None)
    if cfg.family == "vlm" and shape_cfg.kind in ("train", "prefill"):
        specs["patch_embeds"] = P(bdim, None, None)
    if cfg.family == "audio" and shape_cfg.kind in ("train", "prefill"):
        specs["frames"] = P(bdim, None, None)
    return specs


def cache_specs(cfg, shape_cfg, mesh, cache_tree) -> Any:
    """Shard KV caches: batch over data axes; heads over model if divisible,
    else the sequence/window dim; SSM inner dim over model."""
    d = data_axes(mesh)

    def one(path, leaf):
        pstr = normalize_path(jax.tree_util.keystr(path))
        s = leaf.shape
        if "conv" in pstr and cfg.family == "ssm":  # (L,B,K-1,Di)
            return NamedSharding(mesh, checked_spec(s, (None, d, None, "model"), mesh))
        if pstr.endswith("/h") and len(s) == 4:  # ssm state (L,B,Di,N)
            return NamedSharding(mesh, checked_spec(s, (None, d, "model", None), mesh))
        if pstr.endswith("/h") and len(s) == 3:  # rglru state (G,B,W)
            return NamedSharding(mesh, checked_spec(s, (None, d, "model"), mesh))
        if pstr.endswith("/h") and len(s) == 2:  # rglru tail state (B,W)
            return NamedSharding(mesh, checked_spec(s, (d, "model"), mesh))
        if "conv" in pstr and len(s) == 4:  # rglru conv (G,B,3,W)
            return NamedSharding(mesh, checked_spec(s, (None, d, None, "model"), mesh))
        if "conv" in pstr and len(s) == 3:  # rglru tail conv (B,3,W)
            return NamedSharding(mesh, checked_spec(s, (d, None, "model"), mesh))
        if len(s) == 5:  # (L,B,S,KVH,hd)
            if _fits(s[3], "model", mesh):
                return NamedSharding(mesh, checked_spec(s, (None, d, None, "model", None), mesh))
            return NamedSharding(mesh, checked_spec(s, (None, d, "model", None, None), mesh))
        if len(s) == 4:  # per-block (B,S,KVH,hd)
            if _fits(s[2], "model", mesh):
                return NamedSharding(mesh, checked_spec(s, (d, None, "model", None), mesh))
            return NamedSharding(mesh, checked_spec(s, (d, "model", None, None), mesh))
        return NamedSharding(mesh, P(*([None] * len(s))))

    return jax.tree_util.tree_map_with_path(one, cache_tree)
