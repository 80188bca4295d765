"""Single-program bench of the paged kernel, on the chip.

    chiprun -- python3 tools/attn_bench.py [--only mixed,decode,dot,write]
        [--against <other checkout>/dynamo_tpu/ops/paged_attention.py]

**mixed** (~2.5 min): one layer's attention of a packed step under one
``jit``: the chain a packed step ran until PR 50 (the rows gathered into
``[B, T]``, the rectangle entry, the tokens gathered back) against the
token-major entry (``starts``), on the same inputs: the median and the best of
30 calls on the host's clock (each holds one dispatch), whether the live
tokens' outputs are equal bit for bit, and the rectangle's kernel alone on
zeros. Shapes: the benchmark's mixed programs (``b8 t512`` and ``b8 t64`` at
32 Q / 8 KV heads, K-EXAONE's ``b16 t512`` at 64 Q heads with and without its
window, SmallThinker's 28 Q / 4 KV, Nemotron's ``b32 t512`` at 32 Q / 2 KV), a
chunk row at depths 0 / 1,024 / 3,584 beside one-token rows.

**decode** (~2 min): a decode program's call (``t = 1``) at 8 / 16 / 32 rows of
512 / 2,048 / 4,096 tokens of context each, at the cells' heads (32 Q / 2 KV,
28 / 4, 20 / 4, 32 / 8, 64 / 8) and under the two cells' windows: microseconds
a call, from ``CALLS`` calls chained inside one program (a call is shorter
than a dispatch) less the same chain without the kernel, beside the bytes the
rows' walks move over 819 GB/s. With ``--against`` the other file's kernel runs
on the same inputs in the same process: its microseconds, and whether the two
outputs are equal bit for bit.

**dot**: whether ``dot_general(p f32, v f32)`` at default precision inside a
kernel, which is how the walk multiplies its probabilities by the values,
equals ``dot_general(p.astype(bf16), v.astype(bf16))`` bit for bit (one bf16
pass of the MXU), with ``Precision.HIGHEST`` as the control that must differ.

**write** (~1 min; PR 60): a packed step's K and V into both pools alone, at
the benchmark's four cache views and two token buckets (a ``b8 t512`` program's
520 tokens: seven decode rows and a chunk of 505 from mid-block; a ``b8 t16``
program's 24): the scatter a step ran until PR 60 (``llama._scatter_kv`` at
``_positions_and_slots``' slots, K then V) against ``ops/kv_write.py``,
microseconds a call from ``CALLS`` calls chained in one program over the
donated pools, the scatter's microseconds an update, the bytes over 819 GB/s
(read and written), and whether every block but the trash block is equal bit
for bit.

Lines go to stdout and to ``chiprun_out/attn_bench.json``. It fails without a
TPU: the kernel is not interpreted here (tests/test_attention_tokens.py does
that)."""
import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.obs.compile_ledger import token_bucket  # noqa: E402
from dynamo_tpu.ops import paged_attention as pa  # noqa: E402

D, BS, NBLK, NL = 128, 16, 512, 2
HBM_BYTES_PER_S = 819e9
CALLS = 64      # kernel calls chained in one program of the decode lines
# (name, Q heads, KV heads, window): the cells' decode shapes.
DECODE_CASES = [
    ("nemo3 32q 2kv", 32, 2, 0), ("st 28q 4kv", 28, 4, 0), ("st 28q 4kv w4096", 28, 4, 4096),
    ("fh1 20q 4kv", 20, 4, 0), ("7b 32q 8kv", 32, 8, 0), ("kex 64q 8kv", 64, 8, 0),
    ("kex 64q 8kv w128", 64, 8, 128)]
DECODE_ROWS, DECODE_DEPTHS = (8, 16, 32), (512, 2048, 4096)


def bench(f, args, reps=30):
    r = f(*args); jax.block_until_ready(r)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); r = f(*args); jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return r, ts[len(ts) // 2] * 1e6, ts[0] * 1e6


def mixed_lines(emit):
    for name, b, t, h, kh, window, decoders in [
            ("7b b8 t512", 8, 512, 32, 8, 0, 1), ("7b b8 t512 7dec", 8, 512, 32, 8, 0, 7),
            ("7b b8 t64", 8, 64, 32, 8, 0, 2),
            ("kex b16 t512 full", 16, 512, 64, 8, 0, 14), ("kex b16 t512 w128", 16, 512, 64, 8, 128, 14),
            ("st b8 t512 w4096", 8, 512, 28, 4, 4096, 6), ("nemo3 b32 t512", 32, 512, 32, 2, 0, 19)]:
        rep = h // kh
        n = token_bucket("mixed", b, t)
        for depth in (0, 1024, 3584):
            rng = np.random.default_rng(depth + b)
            nb = b * NBLK // 2 + 1
            q_start = np.zeros(b, np.int32); q_len = np.zeros(b, np.int32)
            for i in range(decoders):
                q_start[i], q_len[i] = 900 + 37 * i, 1
            chunk = min(t, n - decoders)
            q_start[decoders], q_len[decoders] = depth, chunk
            tables = 1 + (rng.permutation(b * NBLK) % (nb - 1)).reshape(b, NBLK).astype(np.int32)
            q = jnp.asarray(rng.standard_normal((n, h, D)), jnp.bfloat16)
            k = jnp.asarray(rng.standard_normal((NL, nb, BS, kh, D)), jnp.bfloat16)
            v = jnp.asarray(rng.standard_normal((NL, nb, BS, kh, D)), jnp.bfloat16)
            ql = jnp.asarray(q_len); qs = jnp.asarray(q_start)
            bt = jnp.asarray(tables)
            layer = jnp.int32(1)

            @jax.jit
            def old(q, k, v, bt, qs, ql, layer):
                lay, _ = llama.token_layout(ql, b, t, n)
                rows = lay.to_rows(q.reshape(n, kh, rep, D)).reshape(b, t, h, D)
                a = pa.paged_attention_kernel(rows, k, v, bt, qs, qs + ql, layer=layer, window=window)
                return lay.to_tokens(a).reshape(n, h * D)

            @jax.jit
            def new(q, k, v, bt, qs, ql, layer):
                lay, _ = llama.token_layout(ql, b, t, n)
                a = pa.paged_attention_kernel(q, k, v, bt, qs, qs + ql, layer=layer, window=window,
                                              starts=lay.starts, t=t)
                return a.reshape(n, h * D)

            @jax.jit
            def kernel_only(rows, k, v, bt, qs, ql, layer):
                return pa.paged_attention_kernel(rows, k, v, bt, qs, qs + ql, layer=layer, window=window)

            args = (q, k, v, bt, qs, ql, layer)
            ro, old_us, old_min = bench(old, args)
            rn, new_us, new_min = bench(new, args)
            rows = jnp.zeros((b, t, h, D), jnp.bfloat16)
            _, ker_us, ker_min = bench(kernel_only, (rows,) + args[1:])
            live = int(q_len.sum())
            ro, rn = np.asarray(ro)[:live].view(np.uint16), np.asarray(rn)[:live].view(np.uint16)
            emit({"case": name, "depth": depth, "n": n, "live": live,
                  "old_chain_us": round(old_us, 1), "old_kernel_alone_us": round(ker_us, 1),
                  "new_us": round(new_us, 1), "old_min_us": round(old_min, 1), "new_min_us": round(new_min, 1),
                  "equal_bits": bool((ro == rn).all()), "differing": int((ro != rn).sum())})


def _chain(kernel, window):
    """``CALLS`` calls in one program, each on the last one's output (a
    dispatch is longer than a call; the layers alternate as a model's do),
    and one call alone for its bits. ``kernel`` None: the chain without the
    kernel, whose time comes off."""
    def one(q, k, v, bt, qs, kl, layer):
        if kernel is None:
            return q
        return kernel(q, k, v, bt, qs, kl, layer=layer, window=window)

    @jax.jit
    def chained(q, k, v, bt, qs, kl):
        def step(i, q):
            out = one(q, k, v, bt, qs, kl, lax.rem(i, jnp.int32(NL)))
            return (q + out * jnp.bfloat16(2 ** -7)).astype(q.dtype)
        return lax.fori_loop(jnp.int32(0), jnp.int32(CALLS), step, q)

    return chained, jax.jit(lambda *a: one(*a, jnp.int32(1)))


def decode_lines(emit, other):
    for name, h, kh, window in DECODE_CASES:
        for b in DECODE_ROWS:
            rng = np.random.default_rng(b + h)
            nb = b * 4096 // BS + 1
            # (the pool is drawn on the device: it is a gigabyte at 32 rows)
            k, v = (jax.random.normal(jax.random.key(b + h + i), (NL, nb, BS, kh, D), jnp.bfloat16)
                    for i in range(2))
            q = jnp.asarray(rng.standard_normal((b, 1, h, D)), jnp.bfloat16)
            tables = np.zeros((b, NBLK), np.int32)
            tables[:, :4096 // BS] = 1 + rng.permutation(nb - 1).reshape(b, -1)
            bt = jnp.asarray(tables)
            _, idle_us, _ = bench(_chain(None, 0)[0], (q, k, v, bt, bt[:, 0], bt[:, 0]), reps=10)
            for depth in DECODE_DEPTHS:
                kl = jnp.full((b,), depth, jnp.int32)
                args = (q, k, v, bt, kl - 1, kl)
                first = max(depth - window, 0) // BS if window else 0
                walked = b * (-(-depth // BS) - first)
                line = {"case": name, "b": b, "depth": depth,
                        "walked_us_at_819": round(
                            walked * 2 * BS * kh * D * 2 / HBM_BYTES_PER_S * 1e6, 2)}
                outs = {}
                for side, kernel in (("this", pa.paged_attention_kernel),
                                     ("other", other and other.paged_attention_kernel)):
                    if kernel is None:
                        continue
                    chained, single = _chain(kernel, window)
                    _, us, best = bench(chained, args, reps=10)
                    line[f"{side}_us"] = round((us - idle_us) / CALLS, 2)
                    line[f"{side}_min_us"] = round((best - idle_us) / CALLS, 2)
                    outs[side] = np.asarray(single(*args)).view(np.uint16)
                line["pct_of_819"] = round(100 * line["walked_us_at_819"] / line["this_us"], 1)
                if "other" in outs:
                    line["equal_bits"] = bool((outs["this"] == outs["other"]).all())
                emit(line)


# (name, layers, KV heads, head width): the cells' cache views.
WRITE_VIEWS = [("7b [16,NB,16,8,128]", 16, 8, 128), ("fh1 [6,NB,16,4,128]", 6, 4, 128),
               ("nemo3 [6,NB,16,2,128]", 6, 2, 128), ("phi4 [9,NB,16,2,640]", 9, 2, 640)]


def write_lines(emit):
    from dynamo_tpu.ops.kv_write import kv_write

    b, nb = 8, 8 * 128 + 1
    for name, layers, kh, d in WRITE_VIEWS:
        for t, rows in ((512, [(900 + 37 * i, 1) for i in range(7)] + [(1029, 505)]),
                        (16, [(900 + 37 * i, 1) for i in range(7)] + [(1029, 16)])):
            n = token_bucket("mixed", b, t)
            rng = np.random.default_rng(kh + d + t)
            qs = jnp.asarray([r[0] for r in rows], jnp.int32)
            ql = jnp.asarray([r[1] for r in rows], jnp.int32)
            bt = jnp.asarray(1 + rng.permutation(nb - 1)[:b * 128].reshape(b, 128), jnp.int32)
            k, v = (jnp.asarray(rng.standard_normal((n, kh, d)), jnp.bfloat16) for _ in range(2))

            def pools():
                return tuple(jax.random.normal(jax.random.key(kh + d + i), (layers, nb, BS, kh, d),
                                               jnp.bfloat16) for i in range(2))

            def scatter(k, v, ck, cv, layer):
                lay, valid = llama.token_layout(ql, b, t, n)
                _, slot = llama._positions_and_slots(lay, valid, qs, bt, BS)
                return llama._scatter_kv(ck, k, slot, layer), llama._scatter_kv(cv, v, slot, layer)

            def blocks(k, v, ck, cv, layer):
                lay, _ = llama.token_layout(ql, b, t, n)
                return kv_write(k, v, ck, cv, bt, qs, qs + ql, lay.starts, layer=layer)

            def chain(one):
                def chained(k, v, ck, cv):
                    def step(i, c):
                        return one(k, v, *c, lax.rem(i, jnp.int32(layers))) if one else c
                    return lax.fori_loop(jnp.int32(0), jnp.int32(CALLS), step, (ck, cv))
                return jax.jit(chained, donate_argnums=(2, 3))

            def timed(one):
                f, c, ts = chain(one), pools(), []
                for _ in range(8):
                    t0 = time.perf_counter()
                    c = f(k, v, *c); jax.block_until_ready(c)
                    ts.append(time.perf_counter() - t0)
                return sorted(ts[1:])[3] * 1e6, c

            idle_us, _ = timed(None)
            old_us, old = timed(scatter)
            new_us, new = timed(blocks)
            live = int(ql.sum())
            moved = 2 * live * kh * d * 2
            line = {"case": name, "n": n, "live": live,
                    "scatter_us": round((old_us - idle_us) / CALLS, 2),
                    "scatter_us_an_update": round((old_us - idle_us) / CALLS / (2 * n), 4),
                    "kv_write_us": round((new_us - idle_us) / CALLS, 2),
                    "bytes_us_at_819": round(2 * moved / HBM_BYTES_PER_S * 1e6, 2),
                    "equal_bits_but_trash": all(
                        bool((np.asarray(o[:, 1:]).view(np.uint16) == np.asarray(w[:, 1:]).view(np.uint16)).all())
                        for o, w in zip(old, new))}
            del old, new
            emit(line)


def dot_line(emit):
    """P.V as the walk's ``head`` writes it (float32 probabilities, the
    values a bf16 pool's widened to float32, default precision) against the
    same product of operands rounded to bf16 first, and at HIGHEST."""
    def kernel(p_ref, v_ref, plain_ref, bf16_ref, highest_ref):
        p, v = p_ref[...], v_ref[...]
        dims = (((1,), (0,)), ((), ()))
        plain_ref[...] = lax.dot_general(p, v, dims, preferred_element_type=jnp.float32)
        bf16_ref[...] = lax.dot_general(p.astype(jnp.bfloat16), v.astype(jnp.bfloat16), dims,
                                        preferred_element_type=jnp.float32)
        highest_ref[...] = lax.dot_general(p, v, dims, precision=lax.Precision.HIGHEST,
                                           preferred_element_type=jnp.float32)

    for r in (8, 512):
        rng = np.random.default_rng(r)
        p = jnp.asarray(np.exp(-rng.exponential(2.0, (r, 512))), jnp.float32)
        v = jnp.asarray(rng.standard_normal((512, D)), jnp.bfloat16).astype(jnp.float32)
        out = jax.ShapeDtypeStruct((r, D), jnp.float32)
        plain, bf16, highest = (np.asarray(x) for x in pl.pallas_call(
            kernel, out_shape=(out, out, out))(p, v))
        emit({"case": "p.v in a kernel", "rows": r,
              "default_equals_bf16_operands": bool((plain.view(np.uint32) == bf16.view(np.uint32)).all()),
              "default_equals_highest": bool((plain.view(np.uint32) == highest.view(np.uint32)).all()),
              "max_abs_default_minus_bf16": float(np.abs(plain - bf16).max()),
              "max_abs_default_minus_highest": float(np.abs(plain - highest).max())})


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--only", default="mixed,decode,dot")
    ap.add_argument("--against", help="another checkout's ops/paged_attention.py")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu":
        raise SystemExit("tools/attn_bench.py times the kernel on a TPU")
    other = None
    if args.against:
        spec = importlib.util.spec_from_file_location("other_paged_attention", args.against)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
    out = []

    def emit(line):
        print(json.dumps(line), flush=True)
        out.append(line)

    parts = args.only.split(",")
    if "dot" in parts:
        dot_line(emit)
    if "decode" in parts:
        decode_lines(emit, other)
    if "mixed" in parts:
        mixed_lines(emit)
    if "write" in parts:
        write_lines(emit)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
