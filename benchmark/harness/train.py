"""Training cells: build the family's model, the optimizer and the compiled
step from a configuration file, drive the step from the seed through its first
steps (the readings `correct` compares), hand the same object to the timed
window, and follow the first steps with the family's plain reference
afterwards."""
from __future__ import annotations

import gc
import json
import math
import os
import statistics
import sys
import time

from . import families, traffic
from .reference import seed_key


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Trainer:
    """The system under test, built once: model, optimizer, planner's
    choice and the compiled step, with the seed's weights and batches."""

    def __init__(self, cfg, mix, chips):
        import jax
        import jax.numpy as jnp

        import bench

        bench.apply_tpu_defaults()  # the trainer's tuned settings, its own
        self.cfg, self.mix, self.chips = cfg, mix, chips
        self.family = families.of(cfg)
        self.counters = {}
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self.model = self.family.training_model(cfg, mix)
        self.opt = bench.build_optimizer(self.model)
        self._plan(bench.DEFAULT_POLICY, cfg["program"], jax)
        self.step = self._make_step()
        self.counters.update(batch_per_chip=self.batch // chips,
                             seq=mix["seq"])

    def _configure(self, policy, head_chunk):
        mcfg = self.model.config  # the program's own remat settings
        mcfg.recompute = policy != "none"
        mcfg.recompute_policy = policy
        mcfg.head_chunk = head_chunk

    def _train_fn(self, ids, labels):
        return self.model.loss(ids, labels)

    def _make_step(self):
        from paddle_tpu.jit import TrainStep

        return TrainStep(self.model, self._train_fn, self.opt)

    def _plan(self, policy, prog, jax):
        """Batch from memory.plan_train_step over the file's candidates."""
        from paddle_tpu import memory as pmem

        seq = self.mix["seq"]

        def factory(cand):
            self._configure(cand.policy, cand.head_chunk)
            aval = jax.ShapeDtypeStruct
            return self._make_step(), (
                aval((cand.batch, seq), jax.numpy.int32),
                aval((cand.batch, seq), jax.numpy.int64))

        # the decision is kept beside the compile cache, so that only the
        # first run in a checkout prices the candidates (one of which the
        # compiler refuses, every time anew, in about a minute)
        from paddle_tpu.device import compile_cache_dir

        cache = compile_cache_dir()
        decision = pmem.plan_train_step(
            factory, [pmem.Candidate(b, policy,
                                     head_chunk=prog.get("head_chunk"))
                      for b in prog["batch_candidates"]],
            cache_path=os.path.join(cache, "memory_plan.json")
            if cache else "",
            cache_extra=(json.dumps(self.cfg, sort_keys=True), seq))
        if not decision.fits:
            raise RuntimeError(f"no planner candidate fits: {decision}")
        self._configure(decision.policy, decision.head_chunk)
        self.batch = decision.batch
        self.counters.update(
            planner_peak_gib=decision.peak_bytes / 2**30,
            planner_batch_tokens=float(decision.batch * seq))
        log(f"plan ({decision.source}): batch {decision.batch} peak "
            f"{decision.peak_bytes / 2**30:.3f} GiB; evaluated "
            f"{[(c['batch'], c.get('fits')) for c in decision.candidates]}")

    # ------------------------------------------------------------- the seed
    def load_seed(self, seed):
        """The seed's weights into the model, fresh optimizer state, and
        the seed's batches on the device."""
        import jax

        self.family.load_training_weights(
            self.model, self.family.make_weights(self.cfg, seed, self.dtype))
        self.step._opt_state = None  # fresh moments for a further seed
        self.step._placed = False    # (benchmark/readings.py loads several)
        self.ids, self.labels = traffic.train_batches(
            self.mix, seed, self.batch, self.cfg["vocab_size"])
        self.seed = seed
        self.k = 0
        jax.block_until_ready(self.ids)

    def call(self):
        """The window's own call and feed: the next batch of the set."""
        i = self.k % self.ids.shape[0]
        self.k += 1
        return self.step(self.ids[i], self.labels[i])._data

    def _leaf_sumsq(self, tree_of):
        import jax
        import jax.numpy as jnp

        f = jax.jit(lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))))
        return {leaf: float(f(tree_of(name)))
                for leaf, name in self.family.TRAIN_PARAMS.items()}

    def first_steps(self, n):
        """Drive the step through its first n steps; returns the program's
        readings: each loss, the first gradient's per-leaf squared norm
        (from the first moment after one step: m1 = (1 - beta1) g1) and
        the per-leaf squared norm of the parameters' change after n."""
        import jax
        import jax.numpy as jnp

        b1 = self.cfg["optimizer"]["beta1"]
        losses, grad = [], None
        for i in range(n):
            losses.append(float(self.call()))
            if i == 0:
                st = self.step._opt_state
                m = self._leaf_sumsq(lambda nm: st[nm]["moment1"])
                grad = {k: v / (1 - b1) ** 2 for k, v in m.items()}
        key = seed_key(self.seed)
        params = dict(self.model.named_parameters())

        def delta(leaf, key, arr):  # the key an operand: one program a leaf
            p0 = self.family.seed_param(self.cfg, key, leaf, self.dtype)
            return jnp.sum(jnp.square(arr.astype(jnp.float32)
                                      - p0.astype(jnp.float32)))

        change = {leaf: float(jax.jit(delta, static_argnums=0)(
            leaf, key, params[name]._data))
            for leaf, name in self.family.TRAIN_PARAMS.items()}
        return {"loss": losses, "grad_sumsq": grad, "change_sumsq": change}

    # ----------------------------------------------------------- the window
    def window(self, seconds, spans):
        """Dispatch steps for ``seconds`` with one step in flight: step k is
        dispatched before the loop blocks on step k-1. The window closes on
        the last step's result and counts every step. Nothing but dispatch
        happens inside, so a host stall longer than a step shows in the rate
        and in the step it hit."""
        ends, last = [], None
        gc.collect()
        gc.freeze()
        gc.disable()
        try:
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                nxt = self.call()
                if last is not None:
                    last.block_until_ready()
                    ends.append(time.perf_counter())
                last = nxt
            last.block_until_ready()
            ends.append(time.perf_counter())
            t1 = ends[-1]
        finally:
            gc.enable()
            gc.unfreeze()
        spans.items.append(("bench.window", t0, t1))
        steps = len(ends)
        tokens = steps * self.batch * self.mix["seq"]
        last_loss = float(last)
        walls = [b - a for a, b in zip([t0] + ends, ends)]
        slow = max(range(steps), key=walls.__getitem__)
        log(f"steps {steps}: wall s min {min(walls):.4f} median "
            f"{statistics.median(walls):.4f} max {walls[slow]:.4f} "
            f"(slowest is step {slow}); last loss {last_loss:.4f}")
        self.counters["model_flops"] = (
            tokens * self.family.train_flops_per_token(self.cfg,
                                                       self.mix["seq"]))
        return {"t0": t0, "t1": t1, "steps": steps, "last_loss": last_loss,
                "e2e": {"train_tokens_per_s_per_chip":
                        tokens / (t1 - t0) / self.chips}}

    def free(self):
        """Drop the program's state so the reference has the chip."""
        for _, p in self.model.named_parameters():
            p._data = None
        self.step = self.model = self.opt = None
        self.ids = self.labels = None
        gc.collect()


# ------------------------------------------------------------- the reference
def reference_readings(cfg, mix, seed, batch, n, mode="f32",
                       rows=slice(None)):
    """The plain reference's readings over the same first n steps (over
    ``rows`` of each batch only, to plant a fault)."""
    import jax.numpy as jnp

    gc.collect()  # an earlier reference's state goes before this one comes
    ids, labels = traffic.train_batches(mix, seed, batch, cfg["vocab_size"])
    ref = families.of(cfg).RefTrainer(cfg, seed, cfg["optimizer"],
                                      cfg["torch_dtype"], mode)
    losses, grad = [], None
    for i in range(n):
        loss, g = ref.step(ids[i % ids.shape[0]][rows],
                           labels[i % ids.shape[0]][rows].astype(jnp.int32))
        losses.append(loss)
        grad = grad or g
    return {"loss": losses, "grad_sumsq": grad,
            "change_sumsq": ref.change_sumsq()}


def compare(prog, ref):
    """The numbers `correct` holds: each step's relative loss gap, and by
    the worst leaf the gap between the program's norm and the reference's
    (not the norm of their difference) over the reference's norm of that
    leaf or of the median leaf, whichever is larger. Leaves whose
    reference gradient is under a thousandth of the median leaf's move by
    round-off alone and are left out of the change."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss{i + 1}_gap"] = abs(a - b) / abs(b)
    rg = {k: v ** 0.5 for k, v in ref["grad_sumsq"].items()}
    med_g = statistics.median(rg.values())

    def worst(key, leaves):
        rn = {k: ref[key][k] ** 0.5 for k in leaves}
        med = statistics.median(rn.values())
        gaps = {k: abs(prog[key][k] ** 0.5 - rn[k]) / max(rn[k], med)
                for k in leaves}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    out["grad_norm_gap"], out["grad_worst_leaf"] = worst("grad_sumsq",
                                                         list(rg))
    moved = [k for k in rg if rg[k] >= 1e-3 * med_g]
    out["change_norm_gap"], out["change_worst_leaf"] = worst("change_sumsq",
                                                             moved)
    log("leaf norms (program/reference): " + "; ".join(
        f"{k} grad {prog['grad_sumsq'][k] ** 0.5:.6g}/{rg[k]:.6g} change "
        f"{prog['change_sumsq'][k] ** 0.5:.6g}/"
        f"{ref['change_sumsq'][k] ** 0.5:.6g}" for k in rg))
    return out


def run(cell, args, env):
    """One run of a training cell; returns the result's parts."""
    import jax

    cfg, mix = cell["config"], cell["mix"]
    spans = env["spans"]
    tr = Trainer(cfg, mix, cell["chips"])
    tr.load_seed(args.seed)
    n = mix["check_steps"]
    prog = tr.first_steps(n)
    log(f"first steps: losses {prog['loss']}")
    jax.block_until_ready(tr.call())  # one more, so the window starts warm
    env["start_window"]()
    w = tr.window(args.seconds, spans)
    env["stop_window"](w["t0"], w["t1"])
    counters = dict(tr.counters)
    batch = tr.batch
    tr.free()
    ref = reference_readings(cfg, mix, args.seed, batch, n)
    numbers = compare(prog, ref)
    numbers["last_loss_finite"] = 0.0 if math.isfinite(w["last_loss"]) else 1.0
    return {"e2e": w["e2e"], "counters": counters, "numbers": numbers,
            "attempted": w["steps"],
            "failed": 0 if math.isfinite(w["last_loss"]) else w["steps"],
            "t0": w["t0"], "t1": w["t1"]}
