"""Bring-up check: CLAQ serving on a TPU, through the normal entry points.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four chips: the 2x2 mesh phase only

One chip, three phases, each reporting on lines of its own:
  (a) kernels:  the compiled fused dequant-GEMM (`prepared_qmatmul`)
      against the XLA reference (`_prepared_ref_qmatmul`, f32 at highest
      matmul precision) on the same chip, for each kind of qwen2_1p5b
      projection at its published shape: 2/3/4-bit single-width
      ("aligned") groups, a 2.5-bit AP mix (permuted groups) and Outlier
      Reservation, in f32 and bf16.  The max abs error must stay within
      TOL times the reference's max magnitude.
  (b) quantize: CLAQ (AP 2.5 bits over 2/4-bit columns + OR) on a
      random-initialised qwen2_1p5b (seeded) at its published widths and
      depth, calibrated on the synthetic calibration set.
  (c) serve:    a ServingEngine on those params answers REQUESTS requests;
      every one must end terminal, and the lowered decode program must
      hold the Pallas kernel (`tpu_custom_call`).  Tokens are not compared
      with a reference: a random model's argmax flips under any numeric
      change, so (a) is the numeric check.
With --chips 4 the script runs only the (data x model) = 2x2 mesh engine
against the single-device engine on the same params (depth cut to
MESH_DEPTH layers, printed): first-step logits within phase (a)'s bf16
tolerance, every request terminal.  Beside it, unchecked, it prints the
gap of the sharded kernel alone and of the unquantized model on the mesh,
which locate where a logits gap comes from.

The last line of stdout is one JSON object, {"ok": true, "device": ...}.
Any failure raises before it, so the script exits non-zero without that
line; so does a host whose JAX finds no TPU.  Everything runs in this one
process, which holds the chip(s).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen2_1p5b"
# max |kernel - reference| allowed, as a fraction of max |reference|: f32
# must agree to accumulation-order noise; bf16 rounds every dequantized
# weight to 8 mantissa bits before the MXU
TOL = {"float32": 1e-4, "bfloat16": 2e-2}
KERNEL_ROWS = 8                      # activation rows: one decode batch
SETTINGS = (          # (name, column bit-widths, OR budget, plan N tile)
    ("2bit", (2,), None, 128), ("3bit", (3,), None, 128),
    ("4bit", (4,), None, 128), ("ap2.5", (2, 3), None, 128),
    ("3bit+or", (3,), 0.1, 128),
    # a user-set plan tile below a legal Mosaic block: launches coarsen it
    ("ap2.5+or-bn32", (2, 3), 0.1, 32))
REQUESTS, SLOTS, MAX_NEW, MAX_LEN = 8, 4, 16, 512
MESH_SHAPE, MESH_DEPTH = (2, 2), 4


def _has_kernel(program_text: str) -> bool:
    """Whether a lowered program holds a compiled Mosaic (Pallas) kernel."""
    return "tpu_custom_call" in program_text


def _line(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def projections(cfg):
    """(kind, rows=out features, cols=in features) of each distinct
    projection shape of a dense block."""
    q = cfg.n_heads * cfg.head_dim
    kinds = (("attn.q", q, cfg.d_model),
             ("attn.kv", cfg.n_kv_heads * cfg.head_dim, cfg.d_model),
             ("attn.o", cfg.d_model, q),
             ("mlp.gate_up", cfg.d_ff, cfg.d_model),
             ("mlp.down", cfg.d_model, cfg.d_ff))
    seen, out = set(), []
    for kind, rows, cols in kinds:
        if (rows, cols) not in seen:
            seen.add((rows, cols))
            out.append((kind, rows, cols))
    return out


def random_quantized(rng, rows, cols, bit_widths, or_bits):
    """A QuantizedTensor in CLAQ's deployment layout (bit-class stripes,
    column permutation, structured outliers) from random codes: phase (a)
    checks the kernel's arithmetic, not the quantizer."""
    import jax.numpy as jnp

    from repro.core import ORConfig, policy
    from repro.core.outlier import topk_per_column_mask
    from repro.core.quantized import build_quantized_tensor

    col_bits = np.resize(np.asarray(bit_widths), cols)
    rng.shuffle(col_bits)
    k_max = 2 ** int(col_bits.max())
    codes = rng.integers(0, 2 ** col_bits[None, :], size=(rows, cols))
    cbs = np.sort(rng.normal(size=(cols, k_max)), axis=1).astype(np.float32)
    cbs = np.where(np.arange(k_max) < 2 ** col_bits[:, None], cbs, np.inf)
    W = jnp.asarray(rng.normal(size=(rows, cols)).astype(np.float32))
    counts = np.zeros(cols, np.int32)
    if or_bits:
        counts, _ = policy.or_reserve_counts(
            jnp.asarray(rng.random(cols), jnp.float32), rows,
            ORConfig(or_bits))
        counts = np.asarray(counts)
    reserved = topk_per_column_mask(W, jnp.asarray(counts))
    return build_quantized_tensor(jnp.asarray(codes, jnp.int32),
                                  jnp.asarray(cbs), col_bits, counts, W,
                                  reserved)


def phase_kernels(cfg, seed: int) -> float:
    """(a): returns the worst error as a fraction of its tolerance."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    worst = 0.0
    for kind, rows, cols in projections(cfg):
        for name, widths, or_bits, bn in SETTINGS:
            pqt = random_quantized(rng, rows, cols, widths,
                                   or_bits).prepare(bn=bn)
            layout = "aligned" if pqt.x_gather_free else "permuted"
            x32 = jnp.asarray(rng.normal(size=(KERNEL_ROWS, cols)),
                              jnp.float32)
            for dtype in (jnp.float32, jnp.bfloat16):
                x = x32.astype(dtype)
                kernel = jax.jit(lambda a, p: ops.prepared_qmatmul(
                    a, p, compute_dtype=a.dtype))
                if not _has_kernel(kernel.lower(x, pqt).as_text()):
                    raise AssertionError(
                        f"{kind} {name}: no compiled kernel in the program")
                y = kernel(x, pqt).astype(jnp.float32)
                with jax.default_matmul_precision("highest"):
                    ref = jax.jit(ops._prepared_ref_qmatmul)(x, pqt)
                err = float(jnp.max(jnp.abs(y - ref)))
                bound = TOL[jnp.dtype(dtype).name] * float(
                    jnp.max(jnp.abs(ref)))
                worst = max(worst, err / bound)
                _line("a.kernel", proj=kind, shape=f"{rows}x{cols}",
                      setting=name, layout=layout, bn=pqt.bn,
                      dtype=jnp.dtype(dtype).name, max_abs_err=f"{err:.6g}",
                      tol=f"{bound:.6g}", ok=err <= bound)
                if not err <= bound:
                    raise AssertionError(
                        f"{kind} {name} {jnp.dtype(dtype).name}: kernel "
                        f"error {err} exceeds {bound}")
    return worst


def phase_quantize(cfg, seed: int):
    """(b): CLAQ as `launch/serve.py --bits 2.5` builds it, plus OR."""
    import jax

    from repro.core import APConfig, CLAQConfig, ORConfig
    from repro.data import calibration_set
    from repro.launch.quantize import claq_quantize
    from repro.models import api

    t0 = time.perf_counter()
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    calib = calibration_set(cfg.vocab, n_segments=8, seq_len=64)
    qcfg = CLAQConfig(bits=2, method="kmeans", kmeans_iters=6,
                      gptq_blocksize=32, ap=APConfig(2.5, 2, 4),
                      orr=ORConfig(0.1))
    qparams, report = claq_quantize(params, cfg, calib, qcfg,
                                    batch_size=calib.shape[0])
    jax.block_until_ready(qparams)
    return qparams, report, time.perf_counter() - t0


def serve(eng, prompts):
    """Submit every prompt, step until the engine drains; returns
    (finished requests in prompt order, wall seconds)."""
    import jax

    t0 = time.perf_counter()
    uids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    while eng.active or len(eng.queue):
        eng.step()
    jax.block_until_ready(eng.cache)
    wall = time.perf_counter() - t0
    fin = eng.take_finished()
    reqs = [fin.get(u) for u in uids]
    if any(r is None or not r.done for r in reqs):
        raise AssertionError("a request never reached a terminal state")
    return reqs, wall


def make_prompts(cfg, seed: int):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=rng.integers(4, 12)).tolist()
            for _ in range(REQUESTS)]


def phase_serve(qparams, cfg, seed: int):
    """(c): two rounds of REQUESTS requests (the first compiles)."""
    from repro.serve import ServingEngine

    eng = ServingEngine(qparams, cfg, n_slots=SLOTS, max_len=MAX_LEN)
    prompts = make_prompts(cfg, seed)
    rounds = []
    for _ in range(2):
        reqs, wall = serve(eng, prompts)
        st = eng.stats()
        rounds.append((sum(r.tokens_out for r in reqs), wall,
                       st["prefill_traces"] + st["decode_traces"],
                       sorted({r.state.value for r in reqs})))
    return eng, rounds


def prefill_logits(params, cfg, tokens, mesh=None):
    """Logits of the first generated token, through the model's prefill
    (under `mesh` when given, as the engine runs its jits)."""
    import jax

    from repro.dist import context as dctx
    from repro.models import api

    cache = api.make_cache(cfg, tokens.shape[0], MAX_LEN)
    step = jax.jit(lambda p, t, c: api.prefill_step(
        p, cfg, {"tokens": t}, c)[0])
    if mesh is None:
        return step(params, tokens, cache)
    with mesh, dctx.use_mesh(mesh):
        return step(params, tokens, cache)


def mesh_kernel_gap(cfg, mesh, seed: int) -> float:
    """Max |sharded - single-device| of the compiled kernel alone, on an
    AP + OR gate/up projection at full width with bf16 activations."""
    import jax
    import jax.numpy as jnp

    from repro.dist.sharding import sharded_qmatmul
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    pqt = random_quantized(rng, cfg.d_ff, cfg.d_model, (2, 4),
                           0.1).prepare()
    x = jnp.asarray(rng.normal(size=(KERNEL_ROWS, cfg.d_model)),
                    jnp.bfloat16)
    one = jax.jit(lambda a, p: ops.qmatmul(a, p, use_kernel=True))(x, pqt)
    with mesh:
        got = jax.jit(lambda a, p: sharded_qmatmul(a, p, mesh))(x, pqt)
    return float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - one.astype(jnp.float32))))


def logits_gap(params, cfg, tokens, mesh):
    """(max |mesh - single-device| first-step logits, max |single|) for
    one params tree served by a single-device and a mesh engine."""
    import jax.numpy as jnp

    from repro.serve import ServingEngine

    one = ServingEngine(params, cfg, n_slots=SLOTS, max_len=MAX_LEN)
    meshed = ServingEngine(params, cfg, n_slots=SLOTS, max_len=MAX_LEN,
                           mesh=mesh)
    ref = prefill_logits(one.params, cfg, tokens).astype(jnp.float32)
    got = prefill_logits(meshed.params, cfg, tokens, mesh).astype(
        jnp.float32)
    return (float(jnp.max(jnp.abs(got - ref))),
            float(jnp.max(jnp.abs(ref))), one, meshed)


def run_mesh(cfg, seed: int) -> None:
    """--chips 4: the 2x2 (data x model) mesh engine against the
    single-device engine on the same quantized params.  Where a gap comes
    from is read off two more comparisons: the kernel alone (sharded vs
    single-device) and the same model unquantized, whose mesh path is all
    XLA partitioning and no kernel."""
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh
    from repro.models import api

    cfg = dataclasses.replace(cfg, n_layers=MESH_DEPTH)
    _line("mesh.depth", layers=cfg.n_layers,
          cut=f"{ARCH} has 28 layers; mesh phase serves {MESH_DEPTH}")
    mesh = make_mesh(MESH_SHAPE, ("data", "model"))
    kgap = mesh_kernel_gap(cfg, mesh, seed)
    _line("mesh.kernel", shape=f"{cfg.d_ff}x{cfg.d_model}", setting="ap2.5+or",
          dtype="bfloat16", max_abs_err=f"{kgap:.6g}", bitwise=kgap == 0.0)

    prompts = make_prompts(cfg, seed)
    width = max(len(p) for p in prompts[:SLOTS])
    tokens = jnp.asarray([p + [0] * (width - len(p))
                          for p in prompts[:SLOTS]], jnp.int32)
    dense = api.init_params(jax.random.PRNGKey(seed), cfg)
    dgap, dmax, _, _ = logits_gap(dense, cfg, tokens, mesh)
    _line("mesh.dense_logits", mesh="x".join(map(str, MESH_SHAPE)),
          max_abs_err=f"{dgap:.6g}", max_abs_ref=f"{dmax:.6g}")
    del dense

    qparams, report, setup = phase_quantize(cfg, seed)
    _line("mesh.quantize", layers=cfg.n_layers,
          mean_bits=f"{report.mean_effective_bits:.4f}",
          setup_s=f"{setup:.3f}")
    err, rmax, one, meshed = logits_gap(qparams, cfg, tokens, mesh)
    bound = TOL["bfloat16"] * rmax
    _line("mesh.logits", mesh="x".join(map(str, MESH_SHAPE)),
          max_abs_err=f"{err:.6g}", tol=f"{bound:.6g}", ok=err <= bound)
    if not err <= bound:
        raise AssertionError(f"mesh logits error {err} exceeds {bound}")
    if not _has_kernel(meshed.lower_decode().as_text()):
        raise AssertionError("no compiled kernel in the mesh decode program")
    r1, w1 = serve(one, prompts)
    rm, wm = serve(meshed, prompts)
    same = sum(a.tokens == b.tokens for a, b in zip(r1, rm))
    _line("mesh.serve", requests=len(rm),
          states=",".join(sorted({r.state.value for r in rm})),
          tokens=sum(r.tokens_out for r in rm), wall_s=f"{wm:.3f}",
          single_wall_s=f"{w1:.3f}", same_tokens=f"{same}/{len(rm)}",
          kernel_in_decode=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache

    _line("setup", device_kind=devices[0].device_kind,
          devices=len(devices), jax=jax.__version__,
          compile_cache=use_compile_cache())
    cfg = get_config(ARCH)
    if args.chips == 4:
        run_mesh(cfg, args.seed)
    else:
        worst = phase_kernels(cfg, args.seed)
        _line("a.kernels", ok=True, worst_err_over_tol=f"{worst:.6g}")
        qparams, report, setup = phase_quantize(cfg, args.seed)
        _line("b.quantize", arch=ARCH, layers=cfg.n_layers,
              d_model=cfg.d_model, d_ff=cfg.d_ff, vocab=cfg.vocab,
              matrices=len(report.stats),
              mean_bits=f"{report.mean_effective_bits:.4f}",
              setup_s=f"{setup:.3f}",
              peak_hbm_bytes=devices[0].memory_stats()["peak_bytes_in_use"])
        eng, rounds = phase_serve(qparams, cfg, args.seed)
        for i, (tokens, wall, compiles, states) in enumerate(rounds):
            _line("c.serve", round=i + 1, requests=REQUESTS, slots=SLOTS,
                  tokens=tokens, wall_s=f"{wall:.3f}",
                  compiles_so_far=compiles, states=",".join(states),
                  device_kind=devices[0].device_kind)
        if not _has_kernel(eng.lower_decode().as_text()):
            raise AssertionError("no compiled kernel in the decode program")
        _line("c.decode_program", tpu_custom_call=True,
              peak_hbm_bytes=devices[0].memory_stats()["peak_bytes_in_use"])
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
