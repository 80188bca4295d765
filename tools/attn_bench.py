"""Single-program bench of the paged kernel's two entries, on the chip.

    chiprun -- python3 tools/attn_bench.py        # ~2.5 min, one chip

One layer's attention of a packed step under one ``jit``: the chain a packed
step ran until PR 50 (the rows gathered into ``[B, T]``, the rectangle entry,
the tokens gathered back) against the token-major entry (``starts``), on the
same inputs: the median and the best of 30 calls on the host's clock (each
holds one dispatch), whether the live tokens' outputs are equal bit for bit,
and the rectangle's kernel alone on zeros. Shapes: the benchmark's mixed
programs (``b8 t512`` and ``b8 t64`` at 32 Q / 8 KV heads, K-EXAONE's
``b16 t512`` at 64 Q heads with and without its window, SmallThinker's 28 Q /
4 KV, Nemotron's ``b32 t512`` at 32 Q / 2 KV), a chunk row at depths 0 /
1,024 / 3,584 beside one-token rows. Lines go to stdout and to
``chiprun_out/attn_bench.json``. It fails without a TPU: the kernel is not
interpreted here (tests/test_attention_tokens.py does that)."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from dynamo_tpu.models import llama  # noqa: E402
from dynamo_tpu.obs.compile_ledger import token_bucket  # noqa: E402
from dynamo_tpu.ops.paged_attention import paged_attention_kernel  # noqa: E402

D, BS, NBLK, NL = 128, 16, 512, 2
dev = jax.devices()[0]
print("device", dev.platform, dev.device_kind, flush=True)
if dev.platform != "tpu":
    raise SystemExit("tools/attn_bench.py times the kernel on a TPU")
out = []

def bench(f, args, reps=30):
    r = f(*args); jax.block_until_ready(r)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); r = f(*args); jax.block_until_ready(r)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return r, ts[len(ts) // 2] * 1e6, ts[0] * 1e6

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
        ql = jnp.asarray(q_len); qs = jnp.asarray(q_start); kl = qs + ql
        bt = jnp.asarray(tables)
        layer = jnp.int32(1)

        @jax.jit
        def old(q, k, v, bt, qs, ql, layer):
            lay, _ = llama.token_layout(ql, b, t, n)
            rows = lay.to_rows(q.reshape(n, kh, rep, D)).reshape(b, t, h, D)
            a = paged_attention_kernel(rows, k, v, bt, qs, qs + ql, layer=layer, window=window)
            return lay.to_tokens(a).reshape(n, h * D)

        @jax.jit
        def new(q, k, v, bt, qs, ql, layer):
            lay, _ = llama.token_layout(ql, b, t, n)
            a = paged_attention_kernel(q, k, v, bt, qs, qs + ql, layer=layer, window=window,
                                       starts=lay.starts, t=t)
            return a.reshape(n, h * D)

        @jax.jit
        def kernel_only(rows, k, v, bt, qs, ql, layer):
            return paged_attention_kernel(rows, k, v, bt, qs, qs + ql, layer=layer, window=window)

        args = (q, k, v, bt, qs, ql, layer)
        ro, old_us, old_min = bench(old, args)
        rn, new_us, new_min = bench(new, args)
        rows = jnp.zeros((b, t, h, D), jnp.bfloat16)
        _, ker_us, ker_min = bench(kernel_only, (rows,) + args[1:])
        live = int(q_len.sum())
        ro, rn = np.asarray(ro)[:live].view(np.uint16), np.asarray(rn)[:live].view(np.uint16)
        line = {"case": name, "depth": depth, "n": n, "live": live,
                "old_chain_us": round(old_us, 1), "old_kernel_alone_us": round(ker_us, 1),
                "new_us": round(new_us, 1), "old_min_us": round(old_min, 1), "new_min_us": round(new_min, 1),
                "equal_bits": bool((ro == rn).all()), "differing": int((ro != rn).sum())}
        print(json.dumps(line), flush=True)
        out.append(line)
os.makedirs("chiprun_out", exist_ok=True)
with open("chiprun_out/attn_bench.json", "w") as f:
    json.dump(out, f, indent=1)
