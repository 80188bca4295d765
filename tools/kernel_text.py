"""A lowered step program as text that two checkouts can be compared by.

    python tools/kernel_text.py <lowered.txt> <out.txt>

``jit(f).lower(...).as_text(debug_info=False)`` holds each Pallas kernel as
the base64 of Mosaic's bytecode, and that bytecode carries the kernel's debug
locations (file, line), so two checkouts' texts differ wherever a line moved.
This writes the text with each kernel's payload cut out, and behind it every
kernel parsed and printed without its debug locations: equal outputs say the
programs are equal outside those locations (PERF.md section 6, PR 50: a
decode program after a change to the kernel's file). CPU only; it compiles
and runs nothing.
"""

from __future__ import annotations

import base64
import re
import sys

from jax._src.interpreters import mlir as jax_mlir
from jax._src.lib import tpu
from jax._src.lib.mlir import ir

_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')


def kernel_text(lowered: str) -> str:
    parts = [_BODY.sub('"body": "<kernel>"', lowered)]
    ctx = jax_mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        for body in _BODY.findall(lowered):
            module = ir.Module.parse(base64.b64decode(body))
            parts.append(module.operation.get_asm(enable_debug_info=False))
    return "\n=====\n".join(parts)


if __name__ == "__main__":
    with open(sys.argv[1]) as f:
        text = kernel_text(f.read())
    with open(sys.argv[2], "w") as f:
        f.write(text)
    print(text.count("\n=====\n"), "kernels")
