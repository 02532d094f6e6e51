"""Runner of training cells: one compiled train step with its state, driven
from the seed through its first steps (which `correct` compares with the
plain reference), then the same object through the measured window.

The configuration file names the program's builder and which of its outputs
is the loss, the optimizer (and the suffix of its first-moment state), the
reference and the function that counts a step's FLOPs; the job file names
the batch and its generator.  With more than one chip the step
goes through ``CompiledProgram.with_data_parallel`` in one process and the
feed is placed pre-sharded.
"""

from __future__ import annotations

import collections
import gc
import importlib
import math
import statistics

import numpy as np

from benchmark import generator, harness


class Trainer:
    """The timed object: executor, program, scope and resident feed."""

    def __init__(self, config, job, devices):
        import jax
        from paddle_tpu import fluid
        from paddle_tpu.fluid.contrib import mixed_precision as mp

        self.config, self.job, self.devices = config, job, devices
        self.n = len(devices)
        self.ref = harness.load_module("reference", config["reference"])
        self.work = harness.load_module(config["work"]["module"])
        b = config["builder"]
        model = importlib.import_module(b["module"])
        self.model_cfg = getattr(model, b["config"])(**b["config_args"])
        self.main, self.startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(self.main, self.startup), \
                fluid.unique_name.guard():
            out = getattr(model, b["build"])(self.model_cfg, is_test=False)
            self.loss = out[int(b["loss_output"])]
            opt = config["optimizer"]
            getattr(fluid.optimizer, opt["name"])(
                learning_rate=opt["learning_rate"]).minimize(self.loss)
        if config["precision"]["bf16_policy"]:
            mp.enable_bf16_policy(self.main)
        tpu = devices[0].platform == "tpu"
        self.place = fluid.TPUPlace(0) if tpu else fluid.CPUPlace()
        self.exe = fluid.Executor(self.place)
        self.program = self.main
        self.sharding = None
        if self.n > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            from paddle_tpu.parallel import mesh as pmesh

            self.program = fluid.CompiledProgram(
                self.main).with_data_parallel(loss_name=self.loss.name,
                                              places=None)
            mesh = pmesh.build_mesh({pmesh.DATA_AXIS: self.n})
            self.sharding = NamedSharding(mesh, P(pmesh.DATA_AXIS))
        self.param_names = sorted(
            p.name for p in self.main.global_block().all_parameters())
        self.scope = None
        self.feed = None
        self.batch = None
        self._jax = jax

    # -- state from the seed -------------------------------------------------

    def reset(self, seed):
        """Fresh optimizer state, the seed's weights and the seed's batch."""
        from paddle_tpu import fluid

        jax = self._jax
        self.scope = fluid.Scope()
        self.exe.run(self.startup, scope=self.scope)
        weights = self.ref.init_weights(self.config, seed)
        have = {n: tuple(np.shape(self.scope.get(n)))
                for n in self.param_names}
        want = {n: tuple(w.shape) for n, w in weights.items()}
        if have != want:
            odd = sorted(set(have.items()) ^ set(want.items()))[:6]
            raise SystemExit(f"train: the program's parameters are not the "
                             f"reference's: {odd}")
        for name, w in weights.items():
            self.scope.set(name, w)
        self.batch = getattr(generator, self.job["generator"])(
            self.job, seed, self.config, shards=self.n)
        if self.sharding is None:
            self.feed = jax.device_put(self.batch, self.devices[0])
        else:
            self.feed = {k: jax.device_put(v, self.sharding)
                         for k, v in self.batch.items()}

    def step(self):
        """One train step through the window's own call; the loss stays on
        the device (nothing blocks)."""
        return self.exe.run(self.program, feed=self.feed,
                            fetch_list=[self.loss.name], scope=self.scope,
                            return_numpy=False)[0]

    def _first_device(self, names):
        return {n: self.scope.get(n).addressable_data(0) for n in names}

    def first_steps(self, seed, steps=3):
        """The program's side of `correct`: each step's loss, the norm of
        the first gradient as Adam got it (moment1 after one step is
        (1 - beta1) x gradient), the norm of the parameters' change."""
        b1 = self.config["optimizer"]["beta1"]
        moment = self.config["optimizer"]["first_moment_suffix"]
        losses, grad_norms = [], None
        for t in range(steps):
            losses.append(float(np.mean(np.asarray(self.step()))))
            if t == 0:
                m1 = self._first_device(
                    [n + moment for n in self.param_names])
                # new arrays: the next step donates the moments' buffers
                first_grads = {k[:-len(moment)]: v / (1.0 - b1)
                               for k, v in m1.items()}
                grad_norms = {k: float(v) for k, v in
                              self.ref.leaf_norms(first_grads).items()}
        start = self.ref.init_weights(self.config, seed)
        now = self._first_device(self.param_names)
        delta = self.ref.leaf_norms(
            {n: now[n] - start[n] for n in self.param_names})
        return {"losses": losses, "grad_norms": grad_norms,
                "first_grads": first_grads, "ref_module": self.ref,
                "delta_norms": {k: float(v) for k, v in delta.items()}}

    def replicas_spread(self):
        """Largest difference between the chips' copies of one parameter's
        norm: 0 where the gradient exchange kept them in step."""
        x = self.scope.get(self.param_names[-1])
        norms = [float(np.linalg.norm(np.asarray(s.data, np.float32)))
                 for s in x.addressable_shards]
        return max(norms) - min(norms)

    def free(self):
        self.scope = self.feed = None
        self.exe = None


def compare(prog, ref):
    """The numbers `correct` holds against limits.

    ``loss_gap``: the largest relative gap of a step's loss.
    ``grad_diff_all``: the norm of the difference of the two first
    gradients over the reference's norm, all leaves together.  The job has
    no dropout, so the two gradients are one function of one input and
    their difference is arithmetic alone; this is the number a lower
    precision fails.
    ``grad_diff``: the same by the worst leaf, each leaf against the larger
    of the reference's norm of that leaf and of the median leaf; it catches
    a fault in one tensor, and a two-element bias can read several times
    the others (PERF.md), so its limit is wide.
    ``delta_gap_all``: the gap between the norms of the parameters' change
    after the steps, all leaves together; held against a step that returns
    its state unchanged, which reads 1."""
    lib = prog["ref_module"]
    out = {"loss_gap": max(abs(a - b) / abs(b) for a, b in
                           zip(prog["losses"], ref["losses"]))}
    med = statistics.median(ref["grad_norms"].values())
    diff = {n: float(v) for n, v in lib.leaf_norms(
        {n: prog["first_grads"][n] - g
         for n, g in ref["first_grads"].items()}).items()}
    rel = {n: v / max(ref["grad_norms"][n], med) for n, v in diff.items()}
    out["grad_diff_leaf"] = max(rel, key=rel.get)
    out["grad_diff"] = rel[out["grad_diff_leaf"]]

    def total(norms):
        return math.sqrt(sum(v * v for v in norms.values()))

    out["grad_diff_all"] = total(diff) / total(ref["grad_norms"])
    out["delta_gap_all"] = (abs(total(prog["delta_norms"])
                                - total(ref["delta_norms"]))
                            / total(ref["delta_norms"]))
    return out


def reference_readings(trainer, seed, steps=3, matmul=None, **kw):
    import jax
    import jax.numpy as jnp

    ref, config = trainer.ref, trainer.config
    with jax.default_matmul_precision("highest"):
        return ref.train_readings(
            config, trainer.batch, seed, steps,
            config["optimizer"]["learning_rate"],
            matmul=matmul or jnp.matmul, shards=trainer.n, **kw)


def control(config, job, devices, seeds, lowprec, seconds):
    """Per seed: the sound program against the reference; the reference
    under other dropout masks, in bfloat16 and in fp8 (the control) against
    itself.  No window is needed: the readings come from the first steps."""
    trainer = Trainer(config, job, devices)
    for seed in seeds:
        trainer.reset(seed)
        prog = trainer.first_steps(seed)
        trainer.scope = trainer.feed = None
        ref = reference_readings(trainer, seed)
        row = {"seed": seed, "program": compare(prog, ref)}
        for name, kw in (
                ("bf16", {"matmul": lowprec.bf16_matmul}),
                ("control_fp8", {"matmul": lowprec.fp8_matmul})):
            low = reference_readings(trainer, seed, **kw)
            low["ref_module"] = trainer.ref
            row[name] = compare(low, ref)
        row["losses"] = {"program": prog["losses"], "reference": ref["losses"]}
        del prog, ref, low  # the next seed's step needs the memory
        gc.collect()
        yield row


def run(ctx):
    config, job, devices = ctx["config"], ctx["mix"], ctx["devices"]
    seed, seconds, checks = ctx["seed"], ctx["seconds"], ctx["checks"]
    jax = __import__("jax")

    trainer = Trainer(config, job, devices)
    trainer.reset(seed)
    prog = trainer.first_steps(seed)
    before = harness.counters()
    in_flight = int(job["steps_in_flight"])
    tokens_per_step = int(job["batch"]) * int(job["seq_len"])

    def drive(until, queue):
        """Dispatch steps until ``until()``; at most ``in_flight`` queued
        ahead of the device.  Returns (steps, seconds inside exe.run)."""
        steps, dispatch = 0, 0.0
        while not until(steps):
            t = harness.now()
            queue.append(trainer.step())
            dispatch += harness.now() - t
            steps += 1
            if len(queue) > in_flight:
                queue.popleft().block_until_ready()
        return steps, dispatch

    queue = collections.deque()
    traced = None
    setup_s = harness.now() - ctx["t_start"]
    t0 = harness.now()
    if not ctx["trace"]:
        steps, dispatch = drive(lambda n: harness.now() - t0 >= seconds,
                                queue)
    else:
        half = seconds / 2.0
        steps, dispatch = drive(lambda n: harness.now() - t0 >= half, queue)
        queue.pop().block_until_ready()  # device drained: the trace is clean
        queue.clear()
        path = harness.trace_dir()
        with harness.tracing(path):
            n_tr, d_tr = drive(lambda n: n >= int(job["trace_steps"]), queue)
            queue[-1].block_until_ready()
        traced = {"dir": path, "steps": n_tr}
        more, d_more = drive(lambda n: harness.now() - t0 >= seconds, queue)
        steps, dispatch = steps + n_tr + more, dispatch + d_tr + d_more
    last = float(np.mean(np.asarray(queue[-1])))  # blocks on the chain
    window_s = harness.now() - t0
    after = harness.counters()

    checks.equal("compiles_in_window",
                 harness.compiles(after) - harness.compiles(before), 0)
    checks.equal("loss_finite", math.isfinite(last), True)
    checks.limit("loss_end_below_first", last, prog["losses"][0])
    report = {e["pass"]: e for e in trainer.main._pass_report
              if e.get("changed")}
    for name, sites in config["expect"]["pass_sites"].items():
        checks.equal(f"pass_sites.{name}",
                     report.get(name, {}).get("sites"), sites)
    forms = harness.kernel_forms(after)
    for primitive, want in config["expect"]["kernel_forms"].items():
        checks.equal(f"kernel_form.{primitive}",
                     sorted(forms.get(primitive, ())), want)
    if trainer.n == 1:
        checks.equal("train_executables",
                     len(trainer.exe.compiled_for(trainer.main)), 1)
    else:
        shards = next(iter(trainer.feed.values())).addressable_shards
        checks.equal("feed_shard_devices",
                     len({s.device for s in shards}), trainer.n)
        checks.equal("replica_norm_spread", trainer.replicas_spread(), 0.0)
    memory = harness.memory_peak_bytes(devices)
    print(f"INFO memory counters {harness.memory_report(devices)}", flush=True)
    trainer.free()

    t_ref = harness.now()
    ref = reference_readings(trainer, seed)
    reference_s = harness.now() - t_ref
    gaps = compare(prog, ref)
    limits = config["correct"]
    for key in ("loss_gap", "grad_diff_all", "grad_diff", "delta_gap_all"):
        checks.limit(key, gaps[key], limits[key])
    print(f"INFO worst leaf of grad_diff {gaps['grad_diff_leaf']}; "
          f"losses program {prog['losses']} "
          f"reference {ref['losses']}; reference took {reference_s:.1f}s",
          flush=True)

    flops_step = getattr(trainer.work,
                         config["work"]["train_step_flops"])(config, job)
    rate = steps * tokens_per_step / window_s / trainer.n
    peak = harness.peaks_for(devices[0].device_kind)["bf16_flops_per_s"] \
        if devices[0].platform == "tpu" else float("nan")
    print(f"INFO steps {steps} in {window_s:.3f}s, {window_s / steps * 1e3:.2f}"
          f" ms/step, {dispatch / steps * 1e3:.2f} ms inside exe.run a step, "
          f"model FLOP/s utilization "
          f"{rate * flops_step / tokens_per_step / peak:.4f} "
          f"(= tokens/s/chip x {flops_step / tokens_per_step:.4g} FLOP/token"
          f" / {peak:.4g})", flush=True)
    return {
        "attempted": steps, "failed": 0, "setup_s": setup_s,
        "memory_peak_bytes": memory,
        "end_to_end": {"train_tokens_per_s_chip": rate},
        "numbers": {**harness.delta(after, before),
                    "host.dispatch_seconds": dispatch,
                    "host.steps": float(steps),
                    "work.flops_per_step_per_chip": flops_step / trainer.n,
                    "work.traced_steps": float(traced["steps"])
                    if traced else 0.0},
        "trace": traced,
    }
