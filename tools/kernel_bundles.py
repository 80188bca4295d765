"""What the TPU's cores execute for the paged-attention kernel, read without a
chip: compile it for the described v5e with libtpu's dump on, and count the
final VLIW bundles of each loop.

    JAX_PLATFORMS=cpu python tools/kernel_bundles.py --out /tmp/bundles          # SmallThinker's decode shape
    JAX_PLATFORMS=cpu python tools/kernel_bundles.py --out /tmp/b --b 16 --h 20 --kh 4
    JAX_PLATFORMS=cpu python tools/kernel_bundles.py --out /tmp/w --kernel kv_write --kh 8 --tokens 520
    python tools/kernel_bundles.py --read <dir>/<...>-final_bundles.txt      # a dump that is there

The compile runs in a child process: libtpu's dumper aborts the process once
the kernel's files are written (a report template it looks for is not
installed), and they are whole by then. Of the hundreds it writes,
``*paged_attention*-final_bundles.txt`` is the program as it runs: one line a
bundle, ``LB:`` where a loop's body begins, one ``>`` a level of nesting. A
loop's line below gives its first and last bundle, the bundles of its own
level (inner loops apart) and of one ``trip`` (those, and the back branch with
its four delay slots where the compiler left them outside the body), and what
stands in them: ``dma`` (copies started), ``wait`` (``dma.done.wait``),
``check`` (``shalt.err`` under a ``BoundsCheck`` comment: a halt if an address
leaves its array), ``sld`` / ``sst`` (scalar loads and stores, a spill's
reloads among them), ``mxu`` (matmul pushes), ``nop`` (empty bundles). Nothing
here is a time: a bundle is an issue slot, and a copy's latency is not in
it."""
import argparse
import glob
import json
import os
import re
import subprocess
import sys

_LINE = re.compile(r"^\s*(?:0x[0-9a-f]+|\d+)\s+(LB|LH|LE|PB|PF|CT)?\s*:\s*(>*)\s*\{(.*)")
_COUNTED = {
    "dma": r"= dma\.(?!done)\w+", "wait": r"dma\.done\.wait", "check": r"shalt\.err",
    "sld": r"= sld ", "sst": r"= sst ", "mxu": r"= vmatmul|= vmatpush|\.mxu",
    "branch": r"= sbr\.",
}


def loops(path: str) -> dict:
    """``{"bundles": n, "loops": [{first, last, depth, own, all, dma, ...}]}``
    of a final-bundles file: a loop is the run of lines from an ``LB:`` on at
    its depth or deeper; its counts are over the lines of its own depth."""
    rows = []
    with open(path) as f:
        for line in f:
            m = _LINE.match(line)
            if not m:
                continue
            mark, depth = m.group(1), len(m.group(2))
            # Comments out (a bounds check's runs on over two more lines).
            body = re.sub(r"/\*.*?(\*/|$)", "", m.group(3)).strip(" }")
            if not body and rows:              # a delay slot carries no marks
                depth = rows[-1][1]
            rows.append((mark, depth, body))
    out = []
    for i, (mark, depth, _) in enumerate(rows):
        if mark != "LB":
            continue
        j = i
        while j + 1 < len(rows) and rows[j + 1][1] >= depth and not (
                rows[j + 1][0] == "LB" and rows[j + 1][1] == depth):
            j += 1
        own = [body for _, d, body in rows[i:j + 1] if d == depth]
        loop = {"first": i, "last": j, "depth": depth, "own": len(own),
                "all": j + 1 - i,
                "nop": sum(not body for body in own)}
        for name, pat in _COUNTED.items():
            loop[name] = sum(len(re.findall(pat, body)) for body in own)
        loop["trip"] = len(own) + (0 if loop.pop("branch") else 5)
        out.append(loop)
    return {"bundles": len(rows), "loops": out}


def _compile(args) -> None:
    """The child: the kernel at one decode or chunk shape, for the described
    v5e, with the dump's flags set before libtpu loads."""
    os.environ["LIBTPU_INIT_ARGS"] = (
        f"--xla_jf_dump_to={args.out} --xla_jf_dump_llo_text=true")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dynamo_tpu.ops.paged_attention import paged_attention_kernel

    sh = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    b, t, d = args.b, args.t, 128
    cache = a((12, 4096, 16, args.kh, d), jnp.bfloat16)
    rows = a((b,), jnp.int32)
    if args.kernel == "kv_write":
        # A packed step's K and V into the pools (ops/kv_write.py): the loop
        # over a row's blocks, and inside it a conditional copy a size.
        from dynamo_tpu.ops.kv_write import kv_write

        new = a((args.tokens or 520, args.kh, d), jnp.bfloat16)
        jax.jit(lambda k, v, ck, cv, bt, qs, kl, ts, layer: kv_write(
            k, v, ck, cv, bt, qs, kl, ts, layer=layer)).lower(
                new, new, cache, cache, a((b, 512), jnp.int32), rows, rows,
                rows, a((), jnp.int32)).compile()
        return
    if args.tokens:
        q, extra, kw = a((args.tokens, args.h, d), jnp.bfloat16), (rows,), {"t": t}
    else:
        q, extra, kw = a((b, t, args.h, d), jnp.bfloat16), (), {}
    jax.jit(lambda q, k, v, bt, qs, kl, layer, *starts: paged_attention_kernel(
        q, k, v, bt, qs, kl, layer=layer, window=args.window,
        **({"starts": starts[0]} if starts else {}), **kw)).lower(
            q, cache, cache, a((b, 512), jnp.int32), rows, rows,
            a((), jnp.int32), *extra).compile()


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", help="directory the dump goes to (made anew)")
    p.add_argument("--read", help="a final_bundles.txt that is there")
    p.add_argument("--b", type=int, default=8)
    p.add_argument("--t", type=int, default=1)
    p.add_argument("--h", type=int, default=28)
    p.add_argument("--kh", type=int, default=4)
    p.add_argument("--window", type=int, default=0)
    p.add_argument("--tokens", type=int, default=0,
                   help="> 0: the token-major entry over this many tokens")
    p.add_argument("--kernel", default="paged_attention",
                   choices=["paged_attention", "kv_write"])
    p.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.child:
        return _compile(args)
    path = args.read
    if not path:
        if not args.out:
            p.error("--out or --read")
        os.makedirs(args.out, exist_ok=True)
        subprocess.run([sys.executable, os.path.abspath(__file__), "--child"]
                       + sys.argv[1:], env={**os.environ, "JAX_PLATFORMS": "cpu"},
                       stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        found = sorted(glob.glob(os.path.join(
            args.out, f"*{args.kernel}*-final_bundles.txt")))
        if not found:
            raise SystemExit(f"no final bundles under {args.out}")
        path = found[-1]
    summary = loops(path)
    print(path)
    print(f"bundles {summary['bundles']}")
    for loop in summary["loops"]:
        print("  " * loop["depth"] + json.dumps(loop))


if __name__ == "__main__":
    main()
