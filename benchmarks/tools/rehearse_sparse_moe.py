"""Compile the two programs (decode step, prefill chunk) of a serving
cell whose runner builds its model through `sparse_moe_program`, at real
size for a DESCRIBED v5e chip (no chip attached), and print their
memory_analysis().  By hand, on the CPU (`tools/rehearse.py` knows its
four programs by name and is not this PR's to edit; its `report` is
used):

    JAX_PLATFORMS=cpu python benchmarks/tools/rehearse_sparse_moe.py WORKLOAD decode|chunk [hlo-out-file]

Nothing runs, so this says nothing about results or times.  As in
rehearse.py, the script answers "tpu" where the program asks for its
backend rather than adding an option to the program, and stands shapes in
for the arrays the engine would place on a device.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmarks.tools.rehearse import report


def compile_program(what, cfg, engine_kw):
    """The engine's own `_compile(what)` for one described chip."""
    from benchmarks.runners import sparse_moe_program
    from bigdl_tpu.serving import DecodeEngine, ModelRegistry
    import bigdl_tpu.serving.decode as dec
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    model = sparse_moe_program.build_model(cfg)
    dt = jnp.dtype(cfg["param_dtype"])
    model._params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, dt),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    reg = ModelRegistry()
    real_init = type(model).ensure_initialized
    type(model).ensure_initialized = lambda self: None
    reg.register("lm", model)
    type(model).ensure_initialized = real_init
    real_zeros = jnp.zeros
    # the engine makes its pool on the device: shapes only here
    jnp.zeros = lambda shape, dtype=None: jax.ShapeDtypeStruct(
        shape, dtype or jnp.float32)
    try:
        eng = DecodeEngine(reg, "lm", **engine_kw)
    finally:
        jnp.zeros = real_zeros
    print("attention route:", eng.kv.attention_path(), flush=True)
    real_jit, captured = jax.jit, {}

    def spy(fn, **kw):
        jitted = real_jit(fn, **kw)

        class Lowerable:
            def lower(self, *args):
                dec.jax.jit = real_jit        # only the engine's own jit
                args = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=chip), args)
                captured["c"] = jitted.lower(*args).compile()

                class Compiled:
                    def compile(self):
                        return captured["c"]
                return Compiled()
        return Lowerable()

    dec.jax.jit = spy
    try:
        eng._compile(what, None)
    finally:
        dec.jax.jit = real_jit
    return captured["c"]


def main(workload, what, out=None):
    from benchmarks import harness
    cell = harness.Cell(workload)
    c = compile_program(what, cell.config, cell.traffic["engine"])
    report(f"{what} program, {cell.workload['config']}", c)
    text = c.as_text()
    print("Mosaic calls:", text.count("tpu_custom_call"))
    if out:
        with open(out, "w") as f:
            f.write(text)


if __name__ == "__main__":
    main(*sys.argv[1:])
