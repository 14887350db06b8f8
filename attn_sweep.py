"""Crossover sweep of the attention core on the chip: XLA against the flash
kernel, the measurement behind ``kernels.attention_dispatch``'s rule.

One layer's attention core, the two paths of ``kernels.attention`` as the
models call it: q/k/v ``[B, T, H, D]`` bf16 (``[B, T, H*D]`` for the kernel), an
all-ones ``[B, T]`` int32 key mask when not causal (the training batch
carries one), forward alone and forward+backward under ``jax.grad``. Each
timing chains ``--layers`` cores in one jitted program (a layer's output is
the next layer's query, so they run in order), runs it ``--reps`` times
back to back and divides the host clock by layers x reps: dispatch hides
behind the device and the figure is device time per layer. B·T = 4,096 and
H·D = 1,024 throughout, the BERT training cell's, unless ``--tokens`` and
``--hidden`` say otherwise (the hybrid model's attention blocks: one
sequence of 8,192, 32 heads of 128).

    chiprun -- python attn_sweep.py                    # the table
    chiprun -- python attn_sweep.py --only 512x64x0    # one row, 24 layers
    chiprun -- python attn_sweep.py --only 8192x128x1 --tokens 8192 --hidden 4096 --layers 2
    chiprun -- python attn_sweep.py --only 8192x192x1 --v-head-dim 128 --tokens 16384 --hidden 4096 --layers 2
    python attn_sweep.py --only 16384x128x1 --tokens 16384 --hidden 4096 --layers 2 --window 1024 --tiles 512x512,1024x1024
    python attn_sweep.py --kernel _parent/deeplearning4j_tpu/kernels/flash_attention.py

Prints one JSON line per row and writes them to
``chiprun_out/attn_sweep.jsonl``. Off the chip it exits 2: a CPU time is
not a device time.
"""
import argparse
import importlib.util
import inspect
import json
import os
import sys
import time

TOKENS, HIDDEN, DTYPE = 4096, 1024, "bfloat16"
SEQS, HEAD_DIMS = (128, 256, 512, 1024, 2048), (64, 128)


def xla_core(q, k, v, mask, causal):
    """The XLA core the models run: ``kernels.attention`` on "xla"."""
    from deeplearning4j_tpu.kernels import attention
    return attention(q, k, v, path="xla", head_dim=q.shape[-1], mask=mask,
                     causal=causal)


def _load_flash(path):
    if path is None:
        from deeplearning4j_tpu.kernels import flash_attention
        return flash_attention
    spec = importlib.util.spec_from_file_location("_swept_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.flash_attention


def time_core(core, T, D, causal, layers, reps, seed, packed=False,
              tokens=TOKENS, hidden=HIDDEN, v_dim=None):
    """(forward ms, forward+backward ms) per layer. ``packed``: q/k/v are
    [B, T, H*D] as the model's flash path keeps them, else [B, T, H, D].
    ``v_dim``: the values' width where it is not D (latent attention; H is
    then ``hidden // v_dim``, operands [B, T, H, .]); a layer's output
    takes the place of the first ``v_dim`` columns of the next query."""
    import jax
    import jax.numpy as jnp
    B, H = tokens // T, hidden // (v_dim or D)
    keys = jax.random.split(jax.random.key(seed), 4)
    shape = (B, T, H * D) if packed else (B, T, H, D)
    q, k, v, ct = (jax.random.normal(kk, shape, jnp.float32)
                   .astype(DTYPE) for kk in keys)
    if v_dim:
        v, ct = v[..., :v_dim], ct[..., :v_dim]
    mask = None if causal else jnp.ones((B, T), jnp.int32)

    def chain(q, k, v):
        x = q
        for _ in range(layers):
            x = core(x, k, v, mask, causal)
            if v_dim:
                x = jnp.concatenate([x, q[..., v_dim:]], axis=-1)
        return x[..., :v_dim] if v_dim else x

    fwd = jax.jit(chain)
    both = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(chain(q, k, v).astype(jnp.float32)
                                * ct.astype(jnp.float32)),
        argnums=(0, 1, 2)))
    out = []
    for fn in (fwd, both):
        jax.block_until_ready(fn(q, k, v))          # compile, warm
        jax.block_until_ready(fn(q, k, v))
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn(q, k, v)
        jax.block_until_ready(r)
        out.append((time.perf_counter() - t0) / (reps * layers) * 1e3)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--kernel", default=None,
                    help="a flash_attention.py to time instead of the "
                         "package's (the parent commit's, say)")
    ap.add_argument("--only", default=None,
                    help="comma-separated rows TxDxCAUSAL, e.g. 512x64x0")
    ap.add_argument("--paths", default="xla,flash")
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tokens", type=int, default=TOKENS,
                    help="B x T of every row")
    ap.add_argument("--hidden", type=int, default=HIDDEN,
                    help="H x D of every row")
    ap.add_argument("--v-head-dim", type=int, default=None,
                    help="the values' width where it is not D (latent "
                         "attention: --only 8192x192x1 --v-head-dim 128 "
                         "--tokens 16384 --hidden 4096); flash only")
    ap.add_argument("--window", type=int, default=None,
                    help="a sliding window (causal rows only); flash only")
    ap.add_argument("--tiles", default=None,
                    help="comma-separated QxK tiles to time the flash "
                         "kernel at, each a row of its own (default: the "
                         "kernel's own choice)")
    ap.add_argument("--out", default="chiprun_out/attn_sweep.jsonl")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"attn_sweep: needs the chip, found {dev.platform}",
              file=sys.stderr)
        return 2
    flash = _load_flash(args.kernel)
    # the tree's kernel takes the packed layout; the parent's has no such
    # argument and gets [B, T, H, D]
    packed = ("head_dim" in inspect.signature(flash).parameters
              and not args.v_head_dim)
    wide = {"v_head_dim": args.v_head_dim} if args.v_head_dim else {}
    if args.window:
        wide["window"] = args.window
    tiles = ([tuple(int(x) for x in t.split("x"))
              for t in args.tiles.split(",")] if args.tiles else [None])

    if args.only:
        rows = [tuple(int(x) for x in r.split("x"))
                for r in args.only.split(",")]
    else:
        rows = [(T, D, c) for D in HEAD_DIMS for T in SEQS for c in (0, 1)]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as f:
        for (T, D, causal), tile in ((r, t) for r in rows for t in tiles):
            rec = {"T": T, "head_dim": D, "causal": bool(causal),
                   "B": args.tokens // T,
                   "H": args.hidden // (args.v_head_dim or D), **wide,
                   "layers": args.layers, "kernel": args.kernel or "tree",
                   "device_kind": dev.device_kind}
            sized = {}
            if tile:
                rec["tiles"] = list(tile)
                sized = {"tile_q": tile[0], "tile_k": tile[1]}

            def flash_core(q, k, v, mask, causal):
                return flash(q, k, v, mask=mask, causal=causal, **wide,
                             **sized, **({"head_dim": D} if packed else {}))

            for name, core in (("xla", xla_core), ("flash", flash_core)):
                if name not in args.paths.split(",") or (
                        wide and name == "xla"):
                    continue
                try:
                    fw, fb = time_core(core, T, D, bool(causal),
                                       args.layers, args.reps, args.seed,
                                       packed and name == "flash",
                                       args.tokens, args.hidden,
                                       args.v_head_dim)
                    rec[f"{name}_fwd_ms"] = round(fw, 4)
                    rec[f"{name}_fwd_bwd_ms"] = round(fb, 4)
                except Exception as e:  # an OOM at long T is a reading too
                    rec[f"{name}_error"] = f"{type(e).__name__}: {e}"[:200]
            line = json.dumps(rec)
            print(line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
