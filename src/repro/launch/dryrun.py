"""Multi-pod dry-run: prove every (arch x shape x mesh) lowers and compiles.

For each combination this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds ShapeDtypeStruct stand-ins for params, optimizer state, inputs
     and caches (``jax.eval_shape`` — zero allocation),
  3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
  4. prints ``memory_analysis()`` (fits?) and ``cost_analysis()`` (FLOPs /
     bytes for the roofline), parses the post-SPMD HLO for collective
     bytes (while-body collectives multiplied by the loop trip count), and
  5. writes a JSON artifact consumed by ``launch.roofline``.

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun

The meshes are 512 placeholder host devices: run as a script, the module
asks XLA for them and pins jax to the CPU platform before anything imports
jax, so this is a compile-only tool on any machine. Importing the module
changes nothing.
"""

import os

if __name__ == "__main__":
    # Before the imports below: importing repro.core initializes the
    # backend, which fixes the device count. Appended, so caller-set XLA
    # flags survive.
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")
    os.environ["JAX_PLATFORMS"] = "cpu"

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.core import channel as channel_lib
from repro.core import transport as transport_lib
from repro.launch import sharding as sh
from repro.launch import steps as steps_lib
from repro.launch.mesh import data_axes, make_production_mesh
from repro.models import registry as R
from repro.optim.sgd import sgd as make_sgd

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1,
    "u64": 8, "u32": 4, "u16": 2, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8\w*|s64|s32|s16|s8|u64|u32|u16|u8|pred|c64|c128)\[([\d,]*)\]")


def _bytes_of_shapes(text: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(text):
        d = m.group(1)
        d = "f8" if d.startswith("f8") else d
        dims = m.group(2)
        n = 1
        if dims:
            for x in dims.split(","):
                n *= int(x)
        total += n * _DTYPE_BYTES[d]
    return total


def parse_collectives(hlo_text: str, default_trip: int) -> dict:
    """Sum collective bytes from post-SPMD HLO, weighting while bodies.

    Returns {op_kind: bytes_per_device} plus {"_total": ...}. Collectives in
    a while-body computation are multiplied by the loop trip count, parsed
    from the condition's comparison constant when recognizable, else
    ``default_trip`` (the layer count — our scans are the only loops).
    """
    # computation name -> list of (kind, result_bytes)
    comps: dict[str, list] = {}
    cur = None
    trip_counts: dict[str, int] = {}  # body computation -> trip count
    cond_const: dict[str, int] = {}  # condition computation -> max constant
    body_of: dict[str, str] = {}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?([\w\.\-]+)\s*(?:\([^)]*\))?\s*->.*{$", ls)
        if (ls.startswith("ENTRY") or (m and ls.endswith("{"))) and "=" not in ls:
            name = ls.split()[1] if ls.startswith("ENTRY") else ls.split()[0]
            cur = name.strip("%").split("(")[0].strip()
            comps.setdefault(cur, [])
            continue
        if ls.startswith("}"):
            continue
        if cur is None:
            continue
        for kind in _COLLECTIVES:
            if f" {kind}(" in ls or ls.startswith(f"{kind}("):
                lhs = ls.split(" = ", 1)[-1]
                shape_part = lhs.split(kind + "(")[0]
                comps[cur].append((kind, _bytes_of_shapes(shape_part)))
                break
        if " while(" in ls:
            mb = re.search(r"body=%?([\w\.\-]+)", ls)
            mc = re.search(r"condition=%?([\w\.\-]+)", ls)
            if mb and mc:
                body_of[mb.group(1)] = mc.group(1)
        mc2 = re.search(r"s32\[\]\s+constant\((\d+)\)", ls)
        if mc2:
            cond_const[cur] = max(cond_const.get(cur, 0), int(mc2.group(1)))

    for body, cond in body_of.items():
        trip_counts[body] = cond_const.get(cond, default_trip) or default_trip

    out: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for comp, items in comps.items():
        mult = trip_counts.get(comp, 1)
        for kind, nbytes in items:
            # ring cost model: AR moves ~2x, others ~1x the buffer
            factor = 2.0 if kind == "all-reduce" else 1.0
            out[kind] += factor * nbytes * mult
    out["_total"] = sum(v for k, v in out.items() if not k.startswith("_"))
    out["_ops"] = sum(len(v) for v in comps.values())
    return out


def build_step_and_args(cfg, shape, mesh, uplink: str, wire_dtype: str = "float32",
                        fsdp_mode: str = "auto"):
    """Returns (fn, arg_shapes (ShapeDtypeStructs), in_shardings, out_shardings)."""
    opt = make_sgd(1e-2)
    key = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(lambda: R.init_params(key, cfg))
    if fsdp_mode == "auto":
        fsdp = uplink != "per_client"
    else:
        fsdp = fsdp_mode == "on"
    pshard = sh.tree_shardings(param_shapes, cfg, mesh, fsdp=fsdp)
    ospec = jax.eval_shape(lambda: opt.init(param_shapes))
    oshard = jax.tree_util.tree_map(
        lambda l: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()), ospec
    )
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    ishapes = R.input_specs(cfg, shape)
    bspecs = sh.batch_specs(cfg, shape, mesh)
    bshard = {k: jax.sharding.NamedSharding(mesh, v) for k, v in bspecs.items()}
    keyspec = jax.ShapeDtypeStruct((2,), jnp.uint32)

    tcfg = transport_lib.TransportConfig(
        mode="approx",
        channel=channel_lib.ChannelConfig(snr_db=10.0),
        chunk_elems=1 << 22,  # bound the PHY live set to ~150 MiB/chunk
        wire_dtype=wire_dtype,
    )

    if shape.kind == "train":
        if uplink == "per_client":
            fn = steps_lib.make_train_step_approx(cfg, opt, tcfg, mesh)
        elif uplink == "per_shard":
            fn = steps_lib.make_train_step(cfg, opt, transport_cfg=tcfg, mesh=mesh)
        else:
            fn = steps_lib.make_train_step(cfg, opt)
        args = (param_shapes, ospec, ishapes, keyspec)
        in_sh = (pshard, oshard, bshard, repl)
        out_sh = (pshard, oshard, repl) + ((repl,) if uplink == "per_client" else ())
        if uplink == "per_client":
            def wrapped(p, o, b, k):
                pp, oo, loss, stats = fn(p, o, b, k)
                return pp, oo, loss, stats
            return wrapped, args, in_sh, (pshard, oshard, repl, repl)
        return fn, args, in_sh, out_sh

    if shape.kind == "prefill":
        fn = steps_lib.make_prefill_step(cfg)
        d = data_axes(mesh)
        out_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
            d if shape.global_batch % _nd(mesh) == 0 else None, "model"
            if cfg.vocab_size % mesh.shape["model"] == 0 else None))
        return fn, (param_shapes, ishapes), (pshard, bshard), out_sh

    # decode
    ring = R.uses_ring_cache(cfg, shape)
    clen = R.cache_len_for(cfg, shape)
    cache_shapes = jax.eval_shape(
        lambda: R.init_cache(cfg, shape.global_batch, clen))
    cshard = sh.cache_specs(cfg, shape, mesh, cache_shapes)
    fn = steps_lib.make_serve_step(cfg, ring=ring)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    d = data_axes(mesh)
    tokshard = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(
        d if shape.global_batch % _nd(mesh) == 0 else None, None))
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def step(params, cache, tokens, pos):
        return fn(params, cache, tokens, pos)

    return (step, (param_shapes, cache_shapes, tok, pos),
            (pshard, cshard, tokshard, repl), (tokshard, cshard))


def _nd(mesh) -> int:
    n = 1
    for a in data_axes(mesh):
        n *= mesh.shape[a]
    return n


def run_one(arch: str, shape_name: str, mesh_kind: str, uplink: str,
            out_dir: str | None, reduced_layers: int = 0,
            overrides: dict | None = None, wire_dtype: str = "float32",
            fsdp_mode: str = "auto") -> dict:
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if reduced_layers:
        # cost-extraction compile: shallow AND unrolled so cost_analysis sees
        # every layer (scan bodies are otherwise counted once)
        over = {"n_layers": reduced_layers, "scan_unroll": True}
        if cfg.encoder_layers:
            over["encoder_layers"] = reduced_layers
        if cfg.first_dense_layers:
            over["first_dense_layers"] = min(cfg.first_dense_layers, 1)
        cfg = dataclasses.replace(cfg, **over)
    shape = INPUT_SHAPES[shape_name]
    ok, reason = R.supports_shape(cfg, shape)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "uplink": uplink,
        "reduced_layers": reduced_layers, "status": "skip", "reason": reason,
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
        "wire_dtype": wire_dtype,
    }
    if not ok:
        print(f"[dryrun] SKIP {arch} x {shape_name}: {reason}")
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    t0 = time.time()
    with jax.set_mesh(mesh):
        fn, args, in_sh, out_sh = build_step_and_args(cfg, shape, mesh, uplink,
                                                      wire_dtype, fsdp_mode)
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # older jax: one dict per program
            cost = cost[0] if cost else {}
        hlo = compiled.as_text()
        coll = parse_collectives(hlo, cfg.n_layers)

    n_chips = int(jnp.prod(jnp.array(list(mesh.shape.values()))))
    rec.update(
        status="ok",
        n_chips=n_chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        flops_per_device=cost.get("flops", 0.0),
        bytes_per_device=cost.get("bytes accessed", 0.0),
        collective_bytes_per_device=coll,
        memory={
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0),
        },
    )
    print(f"[dryrun] OK {arch} x {shape_name} x {mesh_kind} (uplink={uplink}, "
          f"L={reduced_layers or cfg.n_layers}): compile {t_compile:.1f}s, "
          f"args {mem.argument_size_in_bytes/2**30:.2f} GiB/dev, "
          f"temp {mem.temp_size_in_bytes/2**30:.2f} GiB/dev, "
          f"flops/dev {cost.get('flops', 0):.3g}, "
          f"coll {coll['_total']/2**20:.1f} MiB/dev")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}__{shape_name}__{mesh_kind}__{uplink}"
        if reduced_layers:
            tag += f"__L{reduced_layers}"
        for k, v in (overrides or {}).items():
            tag += f"__{k}-{v}"
        if wire_dtype != "float32":
            tag += f"__wire-{wire_dtype}"
        if fsdp_mode != "auto":
            tag += f"__fsdp-{fsdp_mode}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def default_uplink(arch: str, shape_name: str) -> str:
    if INPUT_SHAPES[shape_name].kind != "train":
        return "none"
    # kimi-k2's 2 TB of weights cannot replicate over the client axes; it
    # uses the per-shard uplink (DESIGN.md Sec. 4) with FSDP sharding.
    return "per_shard" if arch == "kimi-k2-1t-a32b" else "per_client"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--uplink", default=None,
                    choices=[None, "none", "per_client", "per_shard"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--reduced-layers", type=int, default=0,
                    help="override layer count (cost-extrapolation compiles)")
    ap.add_argument("--moe-impl", default="", choices=["", "dense", "expert_parallel"])
    ap.add_argument("--attn-impl", default="", choices=["", "naive", "blockwise"])
    ap.add_argument("--wire-dtype", default="float32", choices=["float32", "bfloat16"])
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    args = ap.parse_args()
    overrides = {}
    if args.moe_impl:
        overrides["moe_impl"] = args.moe_impl
    if args.attn_impl:
        overrides["attn_impl"] = args.attn_impl

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch in archs:
        for shape in shapes:
            for mk in meshes:
                uplink = args.uplink or default_uplink(arch, shape)
                try:
                    run_one(arch, shape, mk, uplink, args.out, args.reduced_layers,
                            overrides or None, args.wire_dtype, args.fsdp)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mk, repr(e)))
                    print(f"[dryrun] FAIL {arch} x {shape} x {mk}: {e}")
                    traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} dry-run failures: {failures}")


if __name__ == "__main__":
    main()
