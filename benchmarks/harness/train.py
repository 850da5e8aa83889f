"""``kind: train``: ``frameworks.jax.train`` inside a local run, as a
user's handler calls it, with one callback that is the clock.

Set-up is everything up to the end of the first ``checked_steps`` steps:
import, weights, compilation, and those steps themselves, which go through
the window's own call and feed and are what the reference follows. The
same trainer, state and stream then run the window: the callback keeps a
few steps in flight, stops the loop once ``--seconds`` have passed, and the
window closes on ``block_until_ready`` of the state.
"""

from __future__ import annotations

import collections
import gc
import sys
import time

import numpy as np

from . import cells
from .traffic import train_batches

B1 = 0.9        # the trainer's TrainConfig defaults, stated here because the
B2 = 0.95       # reference follows the same published optimizer settings
WARMUP_STEPS = 10
GRAD_CLIP = 1.0
TINY = 1e-30
# steps the host may be ahead of the device inside the window: one run in
# fourteen lost 3 s to a stall of the host with a single step in flight
# (PERF.md section 6, PR 26); three steps of queued work ride it out
IN_FLIGHT = 3


def _adam_state(opt_state):
    """The optimizer's moments, wherever the chain keeps them."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise LookupError("no Adam moments in the optimizer's state")


def make_clock(seconds: float, checked_steps: int, compiles, tracer,
               trace_steps: int, process_start: float):
    """The callback, built late so that importing this module does not
    import the program."""
    import jax

    from mlrun_tpu.frameworks._common.callbacks import Callback

    from .common import stamp

    class Clock(Callback):
        def __init__(self):
            self.losses, self.error = [], None
            self.lora_before = self.first_mu = self.lora_after = None
            self.opened = self.closed = self.setup_s = None
            self.steps = self._completed = 0
            self._pending = collections.deque()
            self._tracing = False

        def on_train_begin(self):
            stamp(process_start, "weights made, step compiled")
            self.lora_before = jax.device_get(self.trainer.state.lora)

        def on_step_end(self, step, metrics):
            try:
                return self._step(step, metrics)
            except Exception as exc:  # noqa: BLE001 - the trainer swallows
                # what a callback raises; keep it for the result
                self.error = repr(exc)
                return False

        def _step(self, step, metrics):
            state = self.trainer.state
            if step < checked_steps:
                self.losses.append(float(metrics["loss"]))
                if step == 0:
                    self.first_mu = jax.device_get(
                        _adam_state(state.opt_state).mu)
                if step == checked_steps - 1:
                    self.lora_after = jax.device_get(state.lora)
                    jax.block_until_ready(state)
                    compiles.mark()
                    self.opened = time.perf_counter()
                    self.setup_s = self.opened - process_start
                    stamp(process_start, "checked steps done, window opens")
                    if tracer is not None:
                        tracer.start()
                        self._tracing = True
                return None
            self.steps += 1
            self._pending.append(metrics["loss"])
            if len(self._pending) > IN_FLIGHT:
                jax.block_until_ready(self._pending.popleft())
                self._completed += 1
            if self._tracing and self._completed >= trace_steps:
                tracer.stop()
                self._tracing = False
            if time.perf_counter() - self.opened >= seconds:
                return False
            return None

        def on_train_end(self, metrics):
            jax.block_until_ready(self.trainer.state)
            self.closed = time.perf_counter()
            if self._tracing:
                tracer.stop()
                self._tracing = False
            self.trainer = None

    return Clock()


# -- correct -------------------------------------------------------------------
def _leaves(tree) -> dict:
    import jax

    return {"/".join(str(getattr(k, "key", k)) for k in path): np.asarray(
        leaf, np.float32)
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _norms(leaves: dict) -> dict:
    return {name: float(np.linalg.norm(leaf.astype(np.float64)))
            for name, leaf in leaves.items()}


def _readings(losses, first_grad, change) -> dict:
    first = _leaves(first_grad)
    return {"losses": list(losses), "first_grad": _norms(first),
            "first_grad_leaves": first, "change": _norms(_leaves(change))}


def _median_nonzero(values) -> float:
    nonzero = sorted(v for v in values if v > 0)
    return nonzero[len(nonzero) // 2] if nonzero else 0.0


def worst_norm_gap(program: dict, ref: dict, only=None) -> tuple[float, str]:
    """The worst leaf's gap between the program's norm and the
    reference's, against the reference's norm of that leaf or of the median
    leaf (of those that are not nought), whichever is larger."""
    floor = _median_nonzero(ref.values())
    worst, where = 0.0, ""
    for name, ref_norm in ref.items():
        if only is not None and name not in only:
            continue
        gap = abs(program[name] - ref_norm) / max(ref_norm, floor, TINY)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def worst_diff_norm(program: dict, ref: dict) -> tuple[float, str]:
    """The worst leaf's norm of (program - reference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    norms = _norms(ref)
    floor = _median_nonzero(norms.values())
    worst, where = 0.0, ""
    for name, leaf in ref.items():
        diff = float(np.linalg.norm(program[name].astype(np.float64)
                                    - leaf.astype(np.float64)))
        gap = diff / max(norms[name], floor, TINY)
        if gap >= worst:
            worst, where = gap, name
    return worst, where


def compare(program: dict, ref: dict) -> dict:
    """The numbers compared. ``program`` and ``ref`` each hold ``losses``,
    ``first_grad`` (norm by leaf), ``first_grad_leaves`` and ``change``
    (norm by leaf)."""
    steps = min(len(program["losses"]), len(ref["losses"]))
    loss_gap = max(abs(program["losses"][i] - ref["losses"][i])
                   for i in range(steps)) if steps else float("inf")
    if steps < len(ref["losses"]):
        loss_gap = float("inf")
    grad_gap, grad_leaf = worst_norm_gap(program["first_grad"],
                                         ref["first_grad"])
    # leaves whose first gradient is nought in the reference (lora_a and the
    # scaling while B = 0) move later by Adam's round-off alone: the change
    # is compared on the others
    floor = 1e-3 * _median_nonzero(ref["first_grad"].values())
    moved = {n for n, g in ref["first_grad"].items() if g >= floor and g > 0}
    change_gap, change_leaf = worst_norm_gap(program["change"],
                                             ref["change"], only=moved)
    diff_gap, diff_leaf = worst_diff_norm(program["first_grad_leaves"],
                                          ref["first_grad_leaves"])
    return {"loss_gap_max": (loss_gap, f"{steps} steps"),
            "first_grad_norm_gap_max": (grad_gap, grad_leaf),
            "first_grad_diff_norm_max": (diff_gap, diff_leaf),
            "lora_change_norm_gap_max": (change_gap,
                                         f"{change_leaf} of {len(moved)}")}


def reference_readings(reference, fields: dict, traffic: dict, seed: int,
                       quant=None, drop_half_batch=False) -> dict:
    """The family's ``reference`` through the checked steps, from the seed
    alone."""
    import jax

    steps = int(traffic["checked_steps"])
    batches = train_batches(seed, steps, int(traffic["batch_size"]),
                            int(traffic["seq_len"]), fields["vocab_size"])
    weights = reference.make_weights(fields, seed)
    lora = reference.make_lora(fields, seed, int(traffic["lora_rank"]),
                               float(traffic["lora_alpha"]))
    losses, first, after = reference.train_steps(
        fields, weights, lora, batches,
        peak_lr=float(traffic["learning_rate"]),
        total_steps=int(traffic["steps"]), warmup_steps=WARMUP_STEPS,
        grad_clip=GRAD_CLIP, b1=B1, b2=B2, quant=quant,
        drop_half_batch=drop_half_batch)
    change = jax.tree_util.tree_map(lambda a, b: a - b, after, lora)
    return _readings(losses, first, change)


def program_readings(clock) -> dict:
    import jax

    first = jax.tree_util.tree_map(lambda m: np.asarray(m) / (1.0 - B1),
                                   clock.first_mu)
    change = jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), clock.lora_after,
        clock.lora_before)
    return _readings(clock.losses, first, change)


def check(cell: dict, fields: dict, program: dict, seed: int) -> dict:
    limits = cell["check"]["limits"]
    ref = reference_readings(
        cells.family_of(cell["config_data"]).reference, fields,
        cell["traffic_data"], seed)
    compared = {}
    for name, (value, where) in compare(program, ref).items():
        limit = float(limits[name])
        compared[name] = {"value": value, "limit": limit,
                          "ok": bool(value <= limit), "where": where}
    compared["loss_gap_max"]["program"] = program["losses"]
    compared["loss_gap_max"]["reference"] = ref["losses"]
    return compared


# -- one run -------------------------------------------------------------------
def drive(cell: dict, seed: int, seconds: float, compiles, tracer,
          process_start: float):
    """Run the program's ``train`` with the clock; returns the clock."""
    import mlrun_tpu

    from .common import stamp

    traffic = cell["traffic_data"]
    family = cells.family_of(cell["config_data"])
    fields = family.fields(cell["config_data"])
    clock = make_clock(seconds, int(traffic["checked_steps"]), compiles,
                       tracer, int(cell.get("trace_steps", 3)),
                       process_start)

    def handler(context):
        from mlrun_tpu.frameworks.jax import train

        stamp(process_start, "handler entered")
        return train(context, model=family.train_model(fields),
                     lora_rank=int(traffic["lora_rank"]),
                     lora_alpha=float(traffic["lora_alpha"]),
                     seq_len=int(traffic["seq_len"]),
                     batch_size=int(traffic["batch_size"]),
                     steps=int(traffic["steps"]),
                     learning_rate=float(traffic["learning_rate"]),
                     seed=seed, log_every=int(traffic["steps"]),
                     callbacks=[clock])

    fn = mlrun_tpu.new_function(f"bench-{cell['name']}", kind="local",
                                handler=handler)
    run = fn.run(local=True)
    state = run.state()
    if state != "completed" and clock.error is None:
        clock.error = f"run {state}: {run.status.error}"
    return clock


def run(cell: dict, layer_metrics: list, args, device: dict,
        process_start: float) -> str:
    from . import common

    traffic = cell["traffic_data"]
    family = cells.family_of(cell["config_data"])
    fields = family.fields(cell["config_data"])
    compiles = common.CompileCounter()
    tracer = common.Tracer(cell["name"]) if args.trace else None
    clock = drive(cell, args.seed, args.seconds, compiles, tracer,
                  process_start)
    compiled = compiles.since_mark()
    peak_bytes = common.memory_peak_bytes(cell["chips"])
    gc.collect()

    metrics, window_s, rate = {}, 0.0, None
    ran = clock.error is None and clock.opened is not None \
        and clock.closed is not None and clock.steps > 0
    if ran:
        window_s = clock.closed - clock.opened
        tokens = clock.steps * int(traffic["batch_size"]) \
            * int(traffic["seq_len"])
        rate = tokens / window_s
        metrics["train_tokens_per_s"] = {"value": rate, "unit": "tokens/s"}
        metrics["setup_s"] = {"value": clock.setup_s, "unit": "s"}
    print(f"[bench] window {window_s:.2f}s steps={clock.steps} "
          f"compiles_in_window={compiled} error={clock.error}",
          file=sys.stderr, flush=True)

    device = dict(device, memory_peak_bytes=peak_bytes)
    breakdown = None
    if args.trace:
        metrics, breakdown = common.traced_metrics(
            tracer, layer_metrics,
            {"cell": cell, "fields": fields, "costs": family.costs,
             "chips": cell["chips"], "window_s": window_s,
             "tokens_per_s": rate,
             "batch_size": int(traffic["batch_size"]),
             "seq_len": int(traffic["seq_len"])},
            device, bool(args.rehearse))

    failed = 0 if ran else 1
    if ran and len(clock.losses) == int(traffic["checked_steps"]):
        compared = check(cell, fields, program_readings(clock), args.seed)
    else:
        compared = {"steps_checked": {
            "value": len(clock.losses),
            "limit": int(traffic["checked_steps"]), "ok": False,
            "where": clock.error}}
    compared["compiles_in_window"] = {"value": compiled, "limit": 0,
                                      "ok": compiled == 0}
    correct = all(entry["ok"] for entry in compared.values())
    common.report_compared(compared)
    return common.result_line(
        correct=correct, attempted=clock.steps or 1, failed=failed,
        metrics=metrics, device=device, compared=compared,
        breakdown=breakdown,
        notes={"window_s": window_s, "steps": clock.steps,
               "error": clock.error})


# -- the readings the limits are set from (readings.py) -----------------------
def readings(cell: dict, seeds: list, controls: int, seconds: float):
    """The program and the reference over each seed, and over the first
    ``controls`` the control and the planted fault in the reference's
    place."""
    from . import common

    family = cells.family_of(cell["config_data"])
    fields = family.fields(cell["config_data"])
    compiles = common.CompileCounter()
    for i, seed in enumerate(seeds):
        clock = drive(cell, seed, seconds, compiles, None,
                      time.perf_counter())
        if clock.error:
            yield {"seed": seed, "error": clock.error}
            continue
        program = program_readings(clock)
        del clock
        gc.collect()
        started = time.perf_counter()
        ref = reference_readings(family.reference, fields,
                                 cell["traffic_data"], seed)
        took = time.perf_counter() - started
        entry = {"seed": seed, "reference_s": took,
                 "program_losses": program["losses"],
                 "reference_losses": ref["losses"]}
        for name, (value, where) in compare(program, ref).items():
            entry[name] = value
            entry[name + ".where"] = where
        if i < controls:
            for label, kw in (("control_int8", {"quant": "int8"}),
                              ("half_batch", {"drop_half_batch": True})):
                other = reference_readings(
                    family.reference, fields, cell["traffic_data"], seed,
                    **kw)
                for name, (value, _w) in compare(other, ref).items():
                    entry[f"{label}.{name}"] = value
        yield entry
        gc.collect()
