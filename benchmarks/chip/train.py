"""Driver for ``kind: train`` mixes: ``Trainer.run`` steps back to back.

Set-up builds the launcher's run (:func:`repro.launch.train.build_run`:
the model, AdamW with its schedule, the m-sync straggler policy, and on
four chips a ``("data",)`` mesh), makes the weights on the device from
``--seed`` in one jitted call, and drives that one trainer through its
first three steps with the window's own call and feed. Those steps are
the ones compared with the reference; two more finish the warm-up.

The feed draws fresh rows of Zipf tokens from ``--seed`` every step. The
window calls ``Trainer.run`` for ``steps_per_call`` steps at a time until
``--seconds`` have passed and ends on ``block_until_ready`` of the final
state. ``train_tokens_per_s`` counts the participating tokens (rows with
a nonzero m-sync weight) of every step over the window's wall time.

``correct``: the plain reference repeats the first three steps in float32
from the same weights and rows. Compared are each step's loss, each
leaf's norm of the first gradient as AdamW received it (read back from
its first moment after one step), and each leaf's norm of the parameter
change after three steps, as the fourth step reads the parameters. A
leaf gap is ``| |prog| - |ref| |`` over the larger of the reference's
norm of that leaf and of the median leaf; the worst leaf counts. Leaves
whose reference gradient is under a thousandth of the median leaf's are
left out of the change. Norms hide rounding that is unbiased element by
element, so the first gradient is also compared as a tensor:
``|prog - ref|`` over the same denominator, worst leaf (``grad_error``).
"""

from __future__ import annotations

import time

import numpy as np

from . import trace as tr
from .cell import (DATA, WEIGHTS, CellRun, Check, out_dir,
                   seed_ints, say)
from .device import memory_peak_bytes

#: leaves whose reference gradient is below this share of the median
#: leaf's move by round-off alone under Adam; their change is not compared
NEGLIGIBLE_GRAD = 1e-3


class ZipfFeed:
    """Rows of Zipf tokens, fresh every step, drawn from one seed."""

    def __init__(self, vocab: int, seq: int, batch: int, exponent: float,
                 seed: int):
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        pmf = ranks ** -exponent
        self.cdf = np.cumsum(pmf / pmf.sum())
        self.seq, self.batch, self.seed = seq, batch, seed
        self.step = 0

    def batch_at(self, step: int):
        u = np.random.default_rng([self.seed, step]).random(
            (self.batch, self.seq + 1))
        toks = np.minimum(np.searchsorted(self.cdf, u, side="right"),
                          len(self.cdf) - 1).astype(np.int32)
        return toks[:, :-1], toks[:, 1:]

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        with jax.profiler.TraceAnnotation("bench.feed"):
            tokens, labels = self.batch_at(self.step)
            self.step += 1
            return {"tokens": tokens, "labels": labels,
                    "loss_mask": np.ones(tokens.shape, np.float32)}


def make_weights(shapes, seed: int, n_layer: int, init: dict, sharding=None):
    """The model's parameters from ``seed`` in one jitted call, in the
    dtype and layout of ``shapes`` (a tree of ShapeDtypeStructs):
    nanoGPT's normal(0, 0.02) for matrices and embeddings, 0.02 /
    sqrt(2 n_layer) for the residual projections, LayerNorm scale 1 and
    bias 0."""
    import jax
    import jax.numpy as jnp

    std = init["std"]
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(i, path, sd, key):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "scale":
            return jnp.ones(sd.shape, sd.dtype)
        if name == "bias":
            return jnp.zeros(sd.shape, sd.dtype)
        s = std / np.sqrt(2 * n_layer) if name in ("wo", "w_down") else std
        return (s * jax.random.normal(jax.random.fold_in(key, i), sd.shape,
                                      jnp.float32)).astype(sd.dtype)

    def build(key):
        return jax.tree_util.tree_unflatten(
            treedef, [leaf(i, p, sd, key) for i, (p, sd) in enumerate(flat)])

    return jax.jit(build, out_shardings=sharding)(jax.random.PRNGKey(seed))


def change_norms(before, after) -> list:
    """Norm of ``after - before`` per leaf (host trees), in float32."""
    import jax

    return [float(np.linalg.norm(np.asarray(a, np.float32).ravel()
                                 - np.asarray(b, np.float32).ravel()))
            for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after))]


def worst_leaf_gap(got, want, keep=None) -> float:
    """Largest ``|got - want|`` over ``max(want_leaf, median(want))``."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf")
    keep = np.ones(want.shape, bool) if keep is None else keep
    floor = np.median(want[keep])
    return float(np.max(np.abs(got - want)[keep]
                        / np.maximum(want[keep], floor)))


def worst_leaf_error(got, want) -> float:
    """Largest ``|got_leaf - want_leaf|`` (tensors, host arrays) over
    ``max(|want_leaf|, median leaf norm)``."""
    norms = np.array([np.linalg.norm(np.asarray(w, np.float64))
                      for w in want])
    errs = np.array([np.linalg.norm(np.asarray(g, np.float64)
                                    - np.asarray(w, np.float64))
                     for g, w in zip(got, want)])
    if not np.all(np.isfinite(errs)):
        return float("inf")
    return float(np.max(errs / np.maximum(norms, np.median(norms))))


class Steps:
    """The launcher's trainer with the benchmark's weights and feed."""

    def __init__(self, cell: dict, cfg: dict, mix: dict, seed: int):
        import jax

        from repro.launch.train import build_run
        from repro.train.trainer import TrainState

        chips = cell["chips"]
        ctx = repl = None
        if chips > 1:
            from repro.launch.mesh import make_mesh_auto
            from repro.sharding.specs import ShardCtx

            mesh = make_mesh_auto((chips,), ("data",))
            ctx = ShardCtx(mesh=mesh, dp_axes=("data",), model_axis=None)
            repl = ctx.sharding()
        self.cfg, self.mix = cfg, mix
        self.B = cfg["batch_per_chip"] * chips
        self.S = cfg["block_size"]
        opt = cfg["optimizer"]
        _, self.trainer, _ = build_run(
            cfg["program_config"], steps=opt["schedule_steps"], batch=self.B,
            seq=self.S, lr=opt["lr"], optimizer=opt["name"],
            policy="m_sync", m=cfg["m"], workers=cfg["workers"],
            time_model=cfg["time_law"], seed=0, ctx=ctx)
        wseed = seed_ints(seed, WEIGHTS, 1)[0]
        dseed = seed_ints(seed, DATA, 1)[0]
        shapes = jax.eval_shape(self.trainer.model.init_params,
                                jax.random.PRNGKey(0))
        params = make_weights(shapes, wseed, cfg["n_layer"], cfg["init"],
                              repl)
        opt_state = jax.jit(self.trainer.optimizer.init,
                            out_shardings=repl)(params)
        self.state = TrainState(params, opt_state, 0)
        self.feed = ZipfFeed(cfg["vocab_size"], self.S, self.B,
                             cfg["data"]["exponent"], dseed)
        # participating tokens per step: m of n groups of B / n rows
        self.tokens_per_step = cfg["m"] * (self.B // cfg["workers"]) * self.S
        self.bad_losses = 0

    def run(self, steps: int, log_every: int):
        hist = self.trainer.run(self.state, self.feed, num_steps=steps,
                                log_every=log_every)
        self.state = self.trainer.final_state
        if set(hist.m_used) != {self.cfg["m"]}:
            raise RuntimeError(f"m-sync used m={sorted(set(hist.m_used))}, "
                               f"configured {self.cfg['m']}")
        self.bad_losses += int(np.sum(~np.isfinite(hist.losses)))
        return hist


def first_steps(work: Steps):
    """Steps 1-3 through the window's call and feed; the program's
    readings: losses, first-gradient leaf norms, parameter change."""
    import jax

    b1 = work.cfg["optimizer"]["b1"]
    p0 = jax.device_get(work.state.params)
    h1 = work.run(1, 1)
    grad = [np.asarray(m, np.float32) / (1.0 - b1) for m in
            jax.tree.leaves(jax.device_get(work.state.opt_state["m"]))]
    h2 = work.run(2, 1)
    p3 = jax.device_get(work.state.params)
    return {"p0": p0,
            "losses": list(h1.losses) + list(h2.losses),
            "grad": grad,
            "grad_norms": [float(np.linalg.norm(g)) for g in grad],
            "change_norms": change_norms(p0, p3)}


def reference_readings(ref, work: Steps, p0, precision="float32") -> dict:
    import jax

    batches = [work.feed.batch_at(i) for i in range(3)]
    out = ref.train_steps(p0, batches, work.cfg, 3, precision)
    grad = [np.asarray(g, np.float32)
            for g in jax.tree.leaves(jax.device_get(out["first_grad"]))]
    return {"losses": out["losses"], "grad": grad,
            "grad_norms": [float(np.linalg.norm(g)) for g in grad],
            "change_norms": change_norms(p0, jax.device_get(out["params"]))}


def compare(prog: dict, want: dict, limits: dict) -> list:
    g_ref = np.asarray(want["grad_norms"])
    keep = g_ref >= NEGLIGIBLE_GRAD * np.median(g_ref)
    loss_gap = float(np.max(np.abs(np.subtract(prog["losses"],
                                               want["losses"]))
                            / np.abs(want["losses"])))
    if not np.all(np.isfinite(prog["losses"])):
        loss_gap = float("inf")
    return [
        Check("loss_gap", loss_gap, limits["loss_gap"]),
        Check("grad_gap", worst_leaf_gap(prog["grad_norms"], g_ref),
              limits["grad_gap"]),
        Check("grad_error", worst_leaf_error(prog["grad"], want["grad"]),
              limits["grad_error"]),
        Check("update_gap", worst_leaf_gap(prog["change_norms"],
                                           want["change_norms"], keep),
              limits["update_gap"]),
    ]


def run_cell(cell, cfg, mix, ref, seed, seconds, trace_on, t0, clock):
    import jax

    work = Steps(cell, cfg, mix, seed)
    say(f"train cell {cell['name']}: batch {work.B}x{work.S}, "
        f"{work.tokens_per_step} participating tokens per step")
    prog = first_steps(work)
    say(f"first three losses {prog['losses']}")
    work.run(mix["warmup_steps"], mix["log_every"])
    jax.block_until_ready(work.state.params)
    setup_s = time.perf_counter() - t0
    say(f"set-up {setup_s:.2f} s")

    clock.reset()
    work.bad_losses = 0
    steps, ends = 0, []
    start = time.perf_counter()
    while True:
        work.run(mix["steps_per_call"], mix["log_every"])
        steps += mix["steps_per_call"]
        ends.append(time.perf_counter())
        if ends[-1] - start >= seconds:
            break
    jax.block_until_ready(work.state.params)
    elapsed = time.perf_counter() - start
    per_call = np.diff([start] + ends)
    rate = work.tokens_per_step * steps / elapsed
    say(f"window: {steps} steps in {elapsed!r} s; calls of "
        f"{mix['steps_per_call']} steps: min {per_call.min():.4f} median "
        f"{np.median(per_call):.4f} max {per_call.max():.4f} s; compile "
        f"events {clock.snapshot()}")
    obs = {"units": steps, "compile": clock.snapshot(),
           "rates": {"train_tokens_per_s": rate},
           "tokens_per_step": work.tokens_per_step}

    busy_s = window_s = breakdown = None
    if trace_on:
        def traced_steps():
            work.run(mix["trace_steps"], mix["log_every"])
            jax.block_until_ready(work.state.params)

        red, window_s = tr.traced_window(
            str(out_dir(cell["name"]) / "trace"), traced_steps)
        busy_s = red["busy_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        obs.update(traced_units=mix["trace_steps"], trace=red,
                   window_s=window_s)

    peak = memory_peak_bytes(jax.local_devices()[:cell["chips"]])
    p0 = prog.pop("p0")
    # the reference runs once the program's state is freed
    work.state = work.trainer.final_state = None
    want = reference_readings(ref, work, p0)
    checks = compare(prog, want, mix["limits"])
    say(f"reference losses {want['losses']}")
    return CellRun(setup_s=setup_s, rates=obs["rates"], attempted=steps,
                   failed=work.bad_losses, checks=checks,
                   memory_peak_bytes=peak, obs=obs, busy_s=busy_s,
                   window_s=window_s, breakdown=breakdown)
