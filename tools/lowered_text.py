#!/usr/bin/env python3
"""Are a change's serving programs the parent's?  Without the chip.

    python tools/lowered_text.py write <checkout> <out dir>
    python tools/lowered_text.py compare <out dir A> <out dir B>

``write`` lowers ``engine._paged_prefill_prog`` and ``engine._paged_decode
_chunk`` of the five drawn towers at their cells' published geometry for a
described v5e, exactly as ``tests/test_paged_layout_aot.py`` of that
checkout does (its helpers, its patches of ``backend.on_tpu`` /
``pallas_interpret``, so the Pallas kernels are in the programs), and
writes a program's StableHLO text (``<tower>.<program>.mlir``) and every
operation's name stack in order (``.names``: the ``jax.named_scope`` s and
transforms a trace's readers find operations by; files and lines dropped).
Run it once on an unpacked parent (``git archive <commit> | tar -x -C
<dir>``) and once on the tree; ~30 s each, one process a checkout.

``compare`` holds two such directories against each other byte for byte,
after printing each Pallas kernel's serialised body (base64 MLIR bytecode
in ``tpu_custom_call``'s ``backend_config``) WITHOUT its locations: a body
carries its call sites' file paths, function names and line numbers, which
differ between two checkouts of one commit.  Exit code: the number of
files that differ.
"""

import base64
import os
import re
import sys

TESTS = {
    "axk1": "test_the_latent_pool_is_stored_as_it_is_computed_on",
    "smallthinker": "test_the_two_kinds_of_pool_are_stored_as_they_are_computed_on",
    "dots3": "test_two_widths_of_latent_rows_are_stored_as_they_are_computed_on",
    "keye": "test_the_kept_rows_are_fetched_from_pools_stored_as_they_are_computed_on",
    "laguna": "test_rows_of_2048_lanes_under_heads_by_kind_are_stored_as_they_are_computed_on",
}
BODY = re.compile(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)')


def _name_stacks(debug_text):
    """``operation  name stack`` a line, in the operations' order."""
    alias = dict(re.findall(r"^(#loc\d*) = loc\((.*)\)$", debug_text, re.M))

    def stack(ref, depth=0):
        body = alias.get(ref, "")
        named = re.match(r'"(jit\([^"]*)"', body)
        if named:
            return named.group(1)
        for child in re.findall(r"#loc\d*", body) if depth < 8 else ():
            found = stack(child, depth + 1)
            if found:
                return found
        return ""

    lines = []
    for line in debug_text.splitlines():
        ref = re.search(r"loc\((#loc\d*)\)\s*$", line)
        if ref and not line.startswith("#loc"):
            op = re.search(r"(stablehlo|func|sdy|chlo)\.[\w.]+|call @\w+", line)
            lines.append(f"{op.group(0) if op else ''}  {stack(ref.group(1))}")
    return "\n".join(lines) + "\n"


def write(root, out):
    root, out = os.path.abspath(root), os.path.abspath(out)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.makedirs(out, exist_ok=True)

    import jax
    import pytest
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_default_matmul_precision", "highest")  # tests/conftest.py's
    import test_paged_layout_aot as aot

    assert aot.engine.__file__.startswith(root), aot.engine.__file__

    class Lowered(Exception):
        pass

    class Program:
        """Stands in for a jitted program: writes what it lowers to and
        stops the test there (nothing is compiled)."""

        tag = None

        def __init__(self, jitted):
            self.jitted = jitted

        def lower(self, *args, **kwargs):
            lowered = self.jitted.lower(*args, **kwargs)
            with open(os.path.join(out, self.tag + ".mlir"), "w") as f:
                f.write(lowered.as_text())
            with open(os.path.join(out, self.tag + ".names"), "w") as f:
                f.write(_name_stacks(lowered.as_text(debug_info=True)))
            raise Lowered

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    aot.engine._paged_decode_chunk = Program(aot.engine._paged_decode_chunk)
    aot.engine._paged_prefill_prog = Program(aot.engine._paged_prefill_prog)
    for tower, test in TESTS.items():
        for program in ("decode_chunk", "prefill"):
            Program.tag = f"{tower}.{program}"
            with pytest.MonkeyPatch.context() as patch:
                try:
                    getattr(aot, test)(chip, program, patch)
                except Lowered:
                    print(Program.tag, "written", flush=True)
                else:
                    raise SystemExit(f"{Program.tag}: nothing was lowered")


def _without_kernel_locations(text):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jaxlib.mlir import ir

    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True  # the bytecode's stable_mosaic wrapper

    def printed(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            body = module.operation.get_asm(enable_debug_info=False)
        return match.group(1) + body + match.group(3)

    return BODY.subn(printed, text)


def compare(a, b):
    differ = 0
    for name in sorted(set(os.listdir(a)) | set(os.listdir(b))):
        try:
            x, y = (open(os.path.join(d, name)).read() for d in (a, b))
        except FileNotFoundError:
            differ += 1
            print("MISSING", name)
            continue
        note = f"{x.count(chr(10))} lines"
        if name.endswith(".mlir"):
            (x, kernels), (y, _) = (_without_kernel_locations(t) for t in (x, y))
            note += f", {kernels} kernel bodies printed without locations"
        differ += x != y
        print("SAME" if x == y else "DIFF", name, f"({note})")
        if x != y:
            for i, (p, q) in enumerate(zip(x.splitlines(), y.splitlines()), 1):
                if p != q:
                    print(f"  line {i}\n   A: {p[:240]}\n   B: {q[:240]}")
                    break
    return differ


if __name__ == "__main__":
    if len(sys.argv) != 4 or sys.argv[1] not in ("write", "compare"):
        raise SystemExit(__doc__)
    sys.exit((write if sys.argv[1] == "write" else compare)(*sys.argv[2:]) or 0)
