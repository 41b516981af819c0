"""Compile a cell's step at real size for a DESCRIBED v5e chip (no chip
attached) and print its memory_analysis().  Run by hand on the CPU:

    JAX_PLATFORMS=cpu python benchmarks/tools/rehearse.py lm|lm_ref|conv|conv_ref

Nothing runs, so this says nothing about results or times.  The program
asks `jax.default_backend()` for its attention route and places its own
parameters; this script steers both (it answers "tpu", and placement is
a no-op) rather than adding an option to the program.
"""
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import json
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding


def shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def report(name, compiled):
    m = compiled.memory_analysis()
    print(f"{name}: compiles for the chip (so it fits its 15.75 GiB); "
          f"arguments {m.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"outputs {m.output_size_in_bytes / 2**30:.2f}, aliased "
          f"{m.alias_size_in_bytes / 2**30:.2f}, temporaries "
          f"{m.temp_size_in_bytes / 2**30:.2f}", flush=True)
    return compiled


def cell_files(config, traffic):
    rd = lambda p: json.load(open(os.path.join(ROOT, "benchmarks", p)))
    return rd(f"configs/{config}.json"), rd(f"traffic/{traffic}.json")


def main(what):
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    jax.default_backend = lambda: "tpu"
    if what == "lm":
        from benchmarks.runners import lm_program
        from bigdl_tpu.optim import AdamW
        from bigdl_tpu.parallel.spmd import SpmdTrainer
        from jax.sharding import Mesh
        import numpy as np
        cfg, tr = cell_files("olmo-1b-l8", "lm-b8-t2048")
        tr.update(json.loads(sys.argv[2]) if len(sys.argv) > 2 else {})
        print("traffic overrides:", sys.argv[2:], flush=True)
        model = lm_program.build_model(cfg, remat=tr["remat"])
        mesh = Mesh(np.array(topo.devices[:1]), ("dp",))
        trainer = SpmdTrainer(model, AdamW(1e-3), mesh=mesh,
                              loss_chunk=tr["loss_chunk"],
                              grad_accum=tr["grad_accum"])
        real_put = jax.device_put
        jax.device_put = lambda x, *a, **k: x
        try:
            trainer.init()
        finally:
            jax.device_put = real_put
        tok = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"]), jnp.int32,
                                   sharding=chip)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        c = trainer._step_fn.lower(shapes(trainer.params, chip),
                                   shapes(trainer.opt_state, chip),
                                   tok, tok, key).compile()
        report("SpmdTrainer step, olmo-1b-l8 B8 T2048", c)
        print("Mosaic calls:", c.as_text().count("tpu_custom_call"))
    elif what == "lm_ref":
        from benchmarks.reference import lm_ref
        cfg, tr = cell_files("olmo-1b-l8", "lm-b8-t2048")
        w = jax.eval_shape(lambda k: lm_ref.make_weights(cfg, k),
                           jax.random.PRNGKey(0))
        ws = shapes(w, chip)
        tok = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"]), jnp.int32,
                                   sharding=chip)
        t = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)

        def step(w, m, v, tok, tgt, t):
            loss, g = lm_ref.loss_and_grads(w, tok, tgt, cfg)
            gn = lm_ref.leaf_norms(g)
            w, m, v = lm_ref.adamw_step(w, g, m, v, t, tr["optimizer"])
            return w, m, v, loss, gn
        c = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            ws, ws, ws, tok, tok, t).compile()
        report("float32 reference step, olmo-1b-l8 B8 T2048", c)
    elif what == "conv":
        from benchmarks.runners import conv_program
        from bigdl_tpu import nn
        from bigdl_tpu.optim import SGD
        from bigdl_tpu.optim.optimizer import make_train_step
        cfg, tr = cell_files("resnet50-imagenet", "imagenet-b256-devfed")
        tr.update(json.loads(sys.argv[2]) if len(sys.argv) > 2 else {})
        model = conv_program.build_model(cfg)
        params, state = jax.eval_shape(lambda: model.init_params(0))
        opt = tr["optimizer"]
        optim = SGD(opt["learning_rate"], momentum=opt["momentum"],
                    dampening=opt["dampening"])
        opt_state = jax.eval_shape(optim.init_state, params)
        step = make_train_step(model, nn.ClassNLLCriterion(), optim,
                               mixed_precision=tr["mixed_precision"],
                               telemetry=True)
        s = cfg["image_size"]
        x = jax.ShapeDtypeStruct((tr["batch"], s, s, 3),
                                 jnp.dtype(tr["input_dtype"]), sharding=chip)
        y = jax.ShapeDtypeStruct((tr["batch"],), jnp.float32, sharding=chip)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=chip)
        c = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            shapes(params, chip), shapes(opt_state, chip),
            shapes(state, chip), x, y, key).compile()
        report(f"LocalOptimizer step, ResNet-50 b{tr['batch']} bf16", c)
    elif what == "conv_ref":
        from benchmarks.reference import resnet_ref
        cfg, tr = cell_files("resnet50-imagenet", "imagenet-b256-devfed")
        w = shapes(jax.eval_shape(lambda k: resnet_ref.make_weights(cfg, k),
                                  jax.random.PRNGKey(0)), chip)
        s = cfg["image_size"]
        x = jax.ShapeDtypeStruct((tr["batch"], s, s, 3),
                                 jnp.dtype(tr["input_dtype"]), sharding=chip)
        y = jax.ShapeDtypeStruct((tr["batch"],), jnp.float32, sharding=chip)
        c = jax.jit(jax.value_and_grad(
            lambda w, x, y: resnet_ref.loss(w, x, y, cfg))).lower(
                w, x, y).compile()
        report(f"float32 reference gradient, ResNet-50 b{tr['batch']}", c)
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    try:
        main(sys.argv[1])
    except Exception as e:
        print("FAILED:", str(e)[:400])
