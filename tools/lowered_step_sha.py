"""sha256 of benchmark cells' training steps LOWERED for a described v5e
(no chip attached, nothing runs): the proof, before any chip time, that a
change leaves an older cell's program what it was.

    JAX_PLATFORMS=cpu python tools/lowered_step_sha.py <tree> <cell> [<cell> ...]

``<tree>`` is a checkout (``git archive <commit> | tar -x -C <dir>``). Unpack
the parent and the change to the SAME path in turn: locations carry file
names. Two digests a cell: ``raw`` of the lowered text as it is, and
``no_loc`` with every Mosaic kernel body (base64 MLIR bytecode in its custom
call's ``backend_config``) re-printed WITHOUT source locations. The bodies
carry the line numbers of the kernel's file and of its callers, so ``raw``
moves with any edit above a ``pallas_call`` in ``ops/`` or ``models/`` even
where no operation changes; ``no_loc`` moves only with the program (PR 32).
State and batch are abstract: ``build``'s jitted initialisers are answered
with ``jax.eval_shape``.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import sys


def _abstract_jit(jax):
    """A ``jax.jit`` whose initialisers (those called with ``out_shardings``)
    return shapes with those shardings instead of running."""
    real_jit, sharding = jax.jit, jax.sharding.Sharding

    class Abstract:
        def __init__(self, fn, **kw):
            self.fn, self.kw, self.jitted = fn, kw, real_jit(fn, **kw)

        def __call__(self, *args):
            out, sh = jax.eval_shape(self.fn, *args), self.kw.get("out_shardings")

            def put(s, o):
                return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=s), o)

            if sh is None or isinstance(sh, sharding):
                return put(sh, out)
            return jax.tree_util.tree_map(
                put, sh, out, is_leaf=lambda x: isinstance(x, sharding))

        def lower(self, *args, **kw):
            return self.jitted.lower(*args, **kw)

    def jit(fn=None, **kw):
        if fn is None:
            return lambda f: jit(f, **kw)
        if "out_shardings" in kw or "donate_argnums" in kw:
            return Abstract(fn, **kw)
        return real_jit(fn, **kw)

    return real_jit, jit


def without_locations(text):
    """``text`` with each Mosaic body replaced by the digest of its assembly
    printed without debug information."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    context = mlir.make_ir_context()
    context.allow_unregistered_dialects = True

    def digest(match):
        with context:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            body = module.operation.get_asm(enable_debug_info=False)
        return ('\\22body\\22: \\22<' + hashlib.sha256(body.encode()).hexdigest()
                + '>\\22')

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', digest, text)


def main(argv):
    root, cells = os.path.abspath(argv[1]), argv[2:]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.chdir(root)
    sys.path.insert(0, root)
    import jax
    from jax.experimental import topologies

    import benchmarks.run as run
    import horovod_tpu as hvd

    devices = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices
    real_jit, abstract_jit = _abstract_jit(jax)
    out = {}
    for cell in cells:
        resolved = run.resolve_cell(run.load_manifest(root), cell, root)
        mesh = hvd.data_parallel_mesh(devices[:resolved["cell"]["chips"]])
        jax.jit = abstract_jit
        try:
            built = resolved["module"].build(
                resolved["config"], resolved["traffic"], mesh, 1)
        finally:
            jax.jit = real_jit
        text = built["step"].lower(*built["state"], *built["batch"]).as_text()
        out[cell] = {
            "raw": hashlib.sha256(text.encode()).hexdigest(),
            "no_loc": hashlib.sha256(
                without_locations(text).encode()).hexdigest()}
        print(cell, json.dumps(out[cell]), flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
