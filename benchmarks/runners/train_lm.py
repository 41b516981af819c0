"""Runner `train_lm`: SpmdTrainer.step() in a loop, as a user writes it.

Set-up builds ONE trainer, gives it the benchmark's seeded weights, and
drives it through its first steps with the window's own call and feed;
after step 1 and step 3 it reads per-leaf norms from the trainer's state.
The same object then runs the window.  The comparison afterwards follows
those first steps with the plain float32 reference.
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks import harness
from benchmarks.reference import lm_ref
from benchmarks.runners import compare, lm_program


class Runner:
    def __init__(self, cell, seed, seconds, devices, probe, scale):
        self.cfg = dict(cell.config, **scale.get("config", {}))
        self.tr = dict(cell.traffic, **scale.get("traffic", {}))
        self.seed, self.seconds = seed, seconds
        self.devices, self.probe = devices, probe

    # -- feed ----------------------------------------------------------- #
    def batch(self, step):
        """Step `step`'s tokens: every row its own draw from the seed."""
        rng = np.random.default_rng([self.seed, step])
        tok = rng.integers(0, self.cfg["vocab_size"],
                           (self.tr["batch"], self.tr["seq_len"] + 1),
                           dtype=np.int32)
        return tok[:, :-1], tok[:, 1:]

    def _step(self, i):
        tokens, targets = self.batch(i)
        with jax.profiler.TraceAnnotation("bench.trainer_step"):
            return self.trainer.step(tokens, targets)

    # -- the run -------------------------------------------------------- #
    def run(self):
        from bigdl_tpu.optim import AdamW
        from bigdl_tpu.parallel.mesh import create_mesh
        from bigdl_tpu.parallel.spmd import SpmdTrainer
        cfg, tr = self.cfg, self.tr
        self.route = lm_program.assert_pallas_route(
            cfg, tr["batch"], tr["seq_len"], self.devices[0].platform)
        model = lm_program.build_model(cfg, remat=tr["remat"])
        opt = tr["optimizer"]
        optim = AdamW(learning_rate=opt["learning_rate"],
                      weight_decay=opt["weight_decay"], beta1=opt["beta1"],
                      beta2=opt["beta2"], epsilon=opt["epsilon"])
        self.trainer = trainer = SpmdTrainer(
            model, optim, mesh=create_mesh(tr["mesh"], devices=self.devices),
            loss_chunk=tr["loss_chunk"], grad_accum=tr["grad_accum"]).init()
        self.probe.mark("trainer_init")
        # the benchmark's weights, made on the device in one call
        self.key = harness.seed_key(self.seed)
        plain = jax.jit(lambda k: lm_ref.make_weights(
            cfg, k, jnp.dtype(cfg["param_dtype"])))(self.key)
        tree = lm_program.to_program_tree(plain, model)
        old = trainer.params
        trainer.params = jax.tree_util.tree_map(
            lambda new, o: jax.device_put(new, o.sharding), tree, old)
        del plain, tree, old
        self.names = lm_program.leaf_names(model)

        norms = jax.jit(lm_ref.leaf_norms)
        n_cmp = tr["compared_steps"]
        losses = []
        for i in range(tr["setup_steps"]):
            losses.append(float(self._step(i)))
            if i < 2:                 # the step's two compiles (PR 21)
                self.probe.mark(f"step{i + 1}")
            if i == 0:
                m1 = jax.device_get(norms(trainer.opt_state["m"]))
            if i == n_cmp - 1:
                dp = jax.device_get(self._delta_norms())
        self.first = {
            "losses": losses[:n_cmp],
            "grad_norms": {n: float(m1[mod][k]) / (1.0 - opt["beta1"])
                           for n, (mod, k) in self.names.items()},
            "dparam_norms": {n: float(dp[mod][k])
                             for n, (mod, k) in self.names.items()}}

        # the window: one step in flight, all steps ready at its close
        cache0 = trainer._step_fn._cache_size()
        step = tr["setup_steps"]
        pending, kept = None, []
        t0 = self.probe.window_open()
        while time.perf_counter() - t0 < self.seconds:
            loss = self._step(step)
            step += 1
            if pending is not None:
                pending.block_until_ready()
            pending = loss
            kept.append(loss)
        jax.block_until_ready((trainer.params, trainer.opt_state))
        t1 = self.probe.window_close()
        self.window_s = t1 - t0
        self.steps = step - tr["setup_steps"]
        self.window_losses = np.asarray(jax.device_get(jnp.stack(kept)))
        self.compiles_in_window = trainer._step_fn._cache_size() - cache0

    def _delta_norms(self):
        """Per-leaf norm of (params - the seed's weights); the seed's
        weights are made again inside the call, not kept."""
        cfg, model = self.cfg, self.trainer.model

        def f(params, key):
            p0 = lm_program.to_program_tree(lm_ref.make_weights(
                cfg, key, jnp.dtype(cfg["param_dtype"])), model)
            return lm_ref.leaf_norms(jax.tree_util.tree_map(
                lambda a, b: a - b, params, p0))
        return jax.jit(f)(self.trainer.params, self.key)

    def results(self):
        tokens = self.steps * self.tr["batch"] * self.tr["seq_len"]
        rate = tokens / self.window_s
        bad = int(np.sum(~np.isfinite(self.window_losses)))
        return {"end_to_end": {"tokens_per_s": rate},
                "attempted": self.steps, "failed": bad,
                "facts": {"tokens_per_s": rate, "steps": self.steps,
                          "window_s": self.window_s,
                          "compiles_in_window": self.compiles_in_window,
                          "batch": self.tr["batch"],
                          "kernel_batch": self.tr["batch"]
                          // self.tr["grad_accum"],
                          "seq_len": self.tr["seq_len"],
                          "attention_route": self.route
                          if self.devices[0].platform == "tpu" else "xla",
                          "config": self.cfg}}

    def release(self):
        self.trainer.detach()
        self.trainer.params = self.trainer.opt_state = None
        self.trainer = None

    def reference(self, quant=None, alter=lambda b: b):
        """The plain reference over the compared steps (`quant`: the
        control's precision; `alter`: a fault planted in its batches)."""
        n_cmp = self.tr["compared_steps"]
        return lm_ref.train_reference(
            self.cfg, self.key, [alter(self.batch(i)) for i in range(n_cmp)],
            self.tr["optimizer"], quant)

    def check(self):
        return compare.training(self.first, self.reference(),
                                self.tr["limits"],
                                self.tr.get("leaf_statistic", "worst"))
