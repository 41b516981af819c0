"""Runner `train_conv`: ONE LocalOptimizer.optimize() call, fed from a
device-resident pool, ended by a deadline trigger of the benchmark's own.

The set-up steps and the window are the same call and the same compiled
step.  A Recorder with an in-memory sink is attached (the telemetry path
users run): every step record floats the loss, so the trigger, which the
loop calls after each record, sees the device's pace.  The weight-stream
hook (`set_weight_stream`, called with the live parameters after every
iteration) is where the first steps' norms are read.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import harness
from benchmarks.reference import lm_ref, resnet_ref
from benchmarks.runners import compare, conv_program


class Runner:
    def __init__(self, cell, seed, seconds, devices, probe, scale):
        self.cfg = dict(cell.config, **scale.get("config", {}))
        self.tr = dict(cell.traffic, **scale.get("traffic", {}))
        self.seed, self.seconds = seed, seconds
        self.devices, self.probe = devices, probe

    def _pool(self, n=None):
        tr = self.tr
        kx = jax.random.fold_in(self.key, 1)
        return jax.jit(lambda k: resnet_ref.make_pool(
            self.cfg, k, n or tr["pool_batches"], tr["batch"],
            jnp.dtype(tr["input_dtype"])))(kx)

    def run(self):
        from bigdl_tpu import nn
        from bigdl_tpu.data.dataset import DataSet
        from bigdl_tpu.observability import InMemorySink, Recorder
        from bigdl_tpu.optim import LocalOptimizer, SGD, Trigger
        cfg, tr, runner = self.cfg, self.tr, self
        opt = tr["optimizer"]
        self.key = harness.seed_key(self.seed)
        model = conv_program.build_model(cfg)
        plain = jax.jit(lambda k: resnet_ref.make_weights(cfg, k))(self.key)
        self.names = conv_program.leaf_names(model, plain)
        # convolution and classifier kernels, told by their shape
        self.kernels = [n for n, shape in lm_ref.leaf_shapes(plain).items()
                        if len(shape) >= 2]
        params, state = conv_program.to_program_tree(plain, model, self.names)
        # optimize() donates the model's buffers: p0 is the kept copy
        self.p0 = jax.tree_util.tree_map(jnp.copy, params)
        model.set_params(params, state)
        jax.block_until_ready(params)
        self.probe.mark("weights")
        xs, ys = self._pool()
        pool = [(xs[i], ys[i]) for i in range(tr["pool_batches"])]
        jax.block_until_ready(pool)
        self.probe.mark("pool")
        del xs, ys, plain

        class DevicePool(DataSet):
            """Device-resident (x, y) tuples, cycled; one epoch is as many
            iterations as ImageNet at this batch."""

            served = 0

            def size(self):
                return tr["iterations_per_epoch"] * tr["batch"]

            def data(self, train=True):
                for _ in range(tr["iterations_per_epoch"]):
                    self.served += 1
                    yield pool[(self.served - 1) % len(pool)]

        norms = jax.jit(lambda a, b: resnet_ref.leaf_norms(
            jax.tree_util.tree_map(lambda p, q: p - q, a, b)))
        n_cmp = tr["compared_steps"]

        class Snapshots:
            """The weight-stream hook: per-leaf norms of the parameters'
            change after the first and after the last compared step."""
            after = {}

            def maybe_publish(self, params, state=None, **_):
                if state.iteration in (1, n_cmp):
                    self.after[state.iteration] = norms(params, runner.p0)
                return False

        class Deadline(Trigger):
            """Opens the window after the set-up steps, ends the run once
            --seconds have passed since."""
            t_open = None
            opened_at = None

            def __call__(self, state):
                if state.iteration == tr["setup_steps"]:
                    self.opened_at = state.iteration
                    self.t_open = runner.probe.window_open()
                if self.t_open is None:
                    return False
                return time.perf_counter() - self.t_open >= runner.seconds

        self.sink = InMemorySink()
        self.snaps, self.deadline = Snapshots(), Deadline()
        optimizer = (LocalOptimizer(model, DevicePool(),
                                    nn.ClassNLLCriterion())
                     .set_optim_method(SGD(learning_rate=opt["learning_rate"],
                                           momentum=opt["momentum"],
                                           dampening=opt["dampening"]))
                     .set_telemetry(Recorder(sinks=[self.sink]))
                     .set_weight_stream(self.snaps)
                     .set_end_when(self.deadline))
        if tr["mixed_precision"]:
            optimizer.set_mixed_precision()
        with jax.profiler.TraceAnnotation("bench.optimize"):
            optimizer.optimize()
        jax.block_until_ready((model._params, model._state))
        t1 = self.probe.window_close()
        self.window_s = t1 - self.deadline.t_open
        self.last_iteration = optimizer.state.iteration
        self.model, self.pool = model, pool

    def results(self):
        tr = self.tr
        steps = self.sink.steps()
        in_window = [s for s in steps if s["step"] > tr["setup_steps"]]
        n = self.last_iteration - tr["setup_steps"]
        rate = n * tr["batch"] / self.window_s
        losses = [s["scalars"]["loss"] for s in in_window]
        span = lambda k: sum(s["spans"].get(k, 0.0) for s in in_window)
        compiles = sum(s["span_counts"].get("train_step_compile", 0)
                       for s in in_window)
        lr_scale = self.tr["optimizer"]["learning_rate"] * (
            1.0 - self.tr["optimizer"]["dampening"])
        after = {k: jax.device_get(v) for k, v in self.snaps.after.items()}
        pick = lambda tree: {n_: float(tree[mod][key])
                             for n_, (mod, key) in self.names.items()}
        self.first = {
            "losses": [s["scalars"]["loss"]
                       for s in steps[:tr["compared_steps"]]],
            # SGD's first step moves each leaf by lr * (1 - dampening) * g
            "grad_norms": {k: v / lr_scale
                           for k, v in pick(after[1]).items()},
            "dparam_norms": pick(after[tr["compared_steps"]])}
        return {"end_to_end": {"images_per_s": rate},
                "attempted": n, "failed": int(np.sum(~np.isfinite(losses))),
                "sound": len(in_window) == n,
                "facts": {"images_per_s": rate, "steps": n,
                          "window_s": self.window_s,
                          "input_wait_s": span("data_fetch") + span("h2d"),
                          "compiles_in_window": compiles,
                          "config": self.cfg}}

    def release(self):
        self.model._params = self.model._state = None
        self.model = self.pool = self.p0 = None
        self.snaps.after.clear()

    def reference(self, quant=None, alter=lambda b: b):
        """The plain reference over the compared steps (`quant`: the
        control's precision; `alter`: a fault planted in its batches)."""
        n_cmp = self.tr["compared_steps"]
        xs, ys = self._pool(min(n_cmp, self.tr["pool_batches"]))
        return resnet_ref.train_reference(
            self.cfg, self.key, [alter((xs[i % len(xs)], ys[i % len(ys)]))
                                 for i in range(n_cmp)],
            self.tr["optimizer"], quant)

    def check(self):
        return compare.training(self.first, self.reference(),
                                self.tr["limits"],
                                self.tr.get("leaf_statistic", "worst"),
                                self.kernels)
