"""``kind: serve``: the paged LLM server behind the serving graph, driven
through ``to_mock_server().test("/v2/models/llm/infer", ...)`` by a closed
loop of clients, as a caller of the graph would.

Set-up builds the graph once (weights, warm-up, one request per prefill
bucket). The window opens as the first client starts, and closes once
every request sent in ``--seconds`` has been answered: it counts all of
them, over all of that time. Then the engine is stopped and freed, and the
plain reference of the configuration's family judges a seeded sample of
what was served.

Beside the common keys a serve cell may carry ``server`` (handed to
``add_model`` as it is) and ``request`` (merged into every request's
body); the whole answer stays in the request's record under ``body``.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

import numpy as np

from . import cells
from .common import percentile
from .traffic import RequestStream

INFER_PATH = "/v2/models/llm/infer"
MODEL_CLASS = "mlrun_tpu.serving.llm.LLMModelServer"
CELL_KEYS = {"server", "request"}


class ServeCell:
    def __init__(self, cell: dict):
        self.cell = cell
        self.family = cells.family_of(cell["config_data"])
        self.fields = self.family.fields(cell["config_data"])
        self.geometry = cell["geometry"]
        self.traffic = cell["traffic_data"]
        self.server = self.engine = self.route = None

    # -- set-up ---------------------------------------------------------------
    def build(self):
        import mlrun_tpu
        from mlrun_tpu.frameworks.jax.auto_trainer import MODEL_PRESETS

        preset = self.cell["config"]
        MODEL_PRESETS[preset] = self.family.preset(self.fields)
        geo = self.geometry
        if int(self.traffic["output_tokens"]) != int(geo["max_new_tokens"]):
            raise cells.CellError(
                "the server fixes max_new_tokens for all requests: the "
                "mix's output_tokens has to equal the geometry's")
        model = dict(
            class_name=MODEL_CLASS, model_preset=preset,
            continuous_batching=True, paged=True,
            page_size=geo["page_size"], slots=geo["slots"],
            max_len=geo["max_len"], n_pages=geo["n_pages"], warmup=True,
            max_new_tokens=geo["max_new_tokens"])
        server = self.cell.get("server", {})
        if set(server) & set(model):
            raise cells.CellError(
                f"server: {sorted(set(server) & set(model))} are set by the "
                f"harness or the cell's geometry")
        fn = mlrun_tpu.new_function(f"bench-{self.cell['name']}",
                                    kind="serving")
        fn.set_topology("router")
        self.route = fn.add_model("llm", **model, **server)
        self.server = fn.to_mock_server()   # weights, warm-up, engine start
        self.engine = self.route.object.engine

    def request(self, prompt: list) -> dict:
        import jax

        sent = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.request"):
            body = self.server.test(
                INFER_PATH, body={**self.cell.get("request", {}),
                                  "inputs": [prompt], "timing": True})
        done = time.perf_counter()
        return {"sent": sent, "done": done, "prompt": prompt,
                "tokens": list(body["outputs"][0]),
                "timing": (body.get("timing") or [None])[0], "body": body}

    def warm_buckets(self):
        """One request per prefill bucket through the graph, so that what
        the host does once per shape is done before the clients start."""
        buckets = sorted({b for b in self.engine.prefill_buckets
                          if b <= int(self.traffic["prompt_tokens"]["max"])}
                         | {min(self.engine.prefill_buckets)})
        rng = np.random.default_rng(0)
        lo = int(self.traffic["prompt_tokens"]["min"])
        threads = []
        errors = []
        for bucket in buckets:
            prompt = rng.integers(1, self.fields["vocab_size"],
                                  max(lo, bucket - 1)).tolist()

            def go(p=prompt):
                try:
                    self.request(p)
                except Exception as exc:  # noqa: BLE001 - reported below
                    errors.append(exc)

            thread = threading.Thread(target=go, daemon=True)
            thread.start()
            threads.append(thread)
        for thread in threads:
            thread.join(timeout=600)
        if errors:
            raise errors[0]

    # -- the window -----------------------------------------------------------
    def window(self, seed: int, seconds: float, on_open=None,
               tracer=None, trace_seconds: float = 0.0) -> dict:
        """The window opens on an idle, warm engine as the first client
        starts; the clients start staggered over the mix's ``ramp_seconds``
        and each sends its next request as the last one answers. When
        ``seconds`` are up nothing more is sent, every request that was
        sent is waited for, and the clock is read after that wait: all of
        the work that was sent counts, over all of that time. The traced
        part begins once the last client has started."""
        traffic = self.traffic
        clients = int(traffic["clients"])
        ramp = float(traffic["ramp_seconds"])
        stream = RequestStream(traffic, self.fields["vocab_size"], seed)
        records, failures = [], []
        lock = threading.Lock()
        stop = threading.Event()
        counter = iter(range(1 << 62))
        traced = None
        if on_open is not None:
            on_open()
        opened = time.perf_counter()

        def client(i: int):
            delay = opened + ramp * i / clients - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            while not stop.is_set():
                with lock:
                    index = next(counter)
                prompt = stream.prompt(index)
                sent = time.perf_counter()
                try:
                    record = self.request(prompt)
                    record["index"] = index
                    with lock:
                        records.append(record)
                except Exception as exc:  # noqa: BLE001 - counted as failed
                    with lock:
                        failures.append({"index": index, "sent": sent,
                                         "done": time.perf_counter(),
                                         "error": repr(exc)[:300]})

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(clients)]
        for thread in threads:
            thread.start()
        if tracer is not None:
            _sleep_until(opened + min(ramp, seconds))
            tracer.start()
            traced = [time.perf_counter()]
            time.sleep(max(0.0, min(trace_seconds, seconds)))
            traced.append(time.perf_counter())
            tracer.stop()
        _sleep_until(opened + seconds)
        stop.set()                          # nothing more is sent
        stats = dict(self.engine.stats)
        deadline = time.perf_counter() + float(
            traffic.get("drain_seconds", 60.0))
        for thread in threads:
            thread.join(timeout=max(0.1, deadline - time.perf_counter()))
        closed = time.perf_counter()        # after the wait for all sent
        hung = sum(thread.is_alive() for thread in threads)
        with lock:
            finished, failed = list(records), list(failures)
        return {"opened": opened, "closed": closed, "finished": finished,
                "failed": failed, "hung": hung, "engine_stats": stats,
                "traced": traced, "seed": seed}

    def close(self):
        """Stop the engine and let go of everything it holds on the chip."""
        if self.engine is not None:
            self.engine.stop()
        for name in ("params", "_pool"):
            if self.engine is not None and hasattr(self.engine, name):
                setattr(self.engine, name, None)
        self.server = self.engine = self.route = None
        gc.collect()


def _sleep_until(when: float):
    rest = when - time.perf_counter()
    if rest > 0:
        time.sleep(rest)


# -- end-to-end metrics --------------------------------------------------------
def end_to_end(result: dict, out_tokens: int) -> tuple[dict, list, list]:
    """(metrics, good, malformed): a request whose answer has not the tokens
    the server fixes, or no timing, counts as failed."""
    window_s = result["closed"] - result["opened"]
    good, malformed = [], []
    for record in result["finished"]:
        timing = record["timing"]
        if len(record["tokens"]) != out_tokens or not timing \
                or "phases" not in timing:
            malformed.append(record)
        else:
            good.append(record)
    metrics = {}
    if good:
        tokens = sum(len(r["tokens"]) for r in good)
        metrics["serve_tokens_per_s"] = {
            "value": tokens / window_s, "unit": "tokens/s"}
        metrics["request_p95_ms"] = {
            "value": 1e3 * percentile(
                [r["done"] - r["sent"] for r in good], 0.95), "unit": "ms"}
    return metrics, good, malformed


# -- correct -------------------------------------------------------------------
def sample_finished(finished: list, seed: int, count: int) -> list:
    """A sample drawn from the seed, with the longest prompt in it."""
    if not finished:
        return []
    ordered = sorted(finished, key=lambda r: r["index"])
    longest = max(ordered, key=lambda r: (len(r["prompt"]), -r["index"]))
    rest = [r for r in ordered if r is not longest]
    rng = np.random.default_rng([int(seed), 3])
    picks = rng.permutation(len(rest))[:max(0, count - 1)]
    return [longest] + [rest[i] for i in sorted(picks)]


def check(cell: dict, fields: dict, finished: list, seed: int) -> dict:
    """What is compared, each beside its limit: the configuration's family
    decides it, over a sample of the finished requests drawn here."""
    sample = sample_finished(finished, seed,
                             int(cell["check"]["sample_requests"]))
    return cells.family_of(cell["config_data"]).serve_check(
        cell, fields, sample)


# -- one run -------------------------------------------------------------------
def run(cell: dict, layer_metrics: list, args, device: dict,
        process_start: float) -> str:
    from . import common

    compiles = common.CompileCounter()
    serving = ServeCell(cell)
    serving.build()
    common.stamp(process_start, "graph built, engine warm")
    serving.warm_buckets()
    common.stamp(process_start, "one request per bucket served")
    tracer = common.Tracer(cell["name"]) if args.trace else None
    opened_at = {}

    def on_open():
        compiles.mark()
        opened_at["setup_s"] = time.perf_counter() - process_start

    result = serving.window(
        args.seed, args.seconds, on_open=on_open, tracer=tracer,
        trace_seconds=float(cell.get("trace_seconds", 3.0)))
    compiled = compiles.since_mark()
    peak_bytes = common.memory_peak_bytes(cell["chips"])
    family, fields = serving.family, serving.fields
    serving.close()

    out_tokens = int(cell["geometry"]["max_new_tokens"])
    metrics, good, malformed = end_to_end(result, out_tokens)
    metrics["setup_s"] = {"value": opened_at["setup_s"], "unit": "s"}
    failed = len(result["failed"]) + len(malformed) + result["hung"]
    attempted = len(result["finished"]) + len(result["failed"]) \
        + result["hung"]
    window_s = result["closed"] - result["opened"]
    print(f"[bench] window {window_s:.2f}s finished={len(good)} "
          f"failed={failed} compiles_in_window={compiled} "
          f"setup_s={opened_at['setup_s']:.1f}", file=sys.stderr, flush=True)

    device = dict(device, memory_peak_bytes=peak_bytes)
    breakdown = None
    if args.trace:
        metrics, breakdown = common.traced_metrics(
            tracer, layer_metrics,
            {"cell": cell, "fields": fields, "costs": family.costs,
             "chips": cell["chips"], "window_s": window_s, "finished": good,
             "traced": result["traced"],
             "engine_stats": result["engine_stats"]},
            device, bool(args.rehearse))

    compared = check(cell, fields, good, args.seed)
    compared["compiles_in_window"] = {"value": compiled, "limit": 0,
                                      "ok": compiled == 0}
    compared["failed_requests"] = {"value": failed, "limit": 0,
                                   "ok": failed == 0}
    correct = all(entry["ok"] for entry in compared.values())
    common.report_compared(compared)
    return common.result_line(
        correct=correct, attempted=attempted, failed=failed,
        metrics=metrics, device=device, compared=compared,
        breakdown=breakdown,
        notes={"window_s": window_s, "finished": len(good),
               "first_failure": (result["failed"] or [{}])[0].get("error")})


# -- the readings the limits are set from (readings.py) -----------------------
def readings(cell: dict, seeds: list, controls: int, seconds: float):
    """One engine, a window per seed; then, the engine freed, the family's
    readings over each window's sample."""
    serving = ServeCell(cell)
    serving.build()
    serving.warm_buckets()
    family, fields = serving.family, serving.fields
    windows = []
    for seed in seeds:
        result = serving.window(seed, seconds)
        _metrics, good, malformed = end_to_end(
            result, int(cell["geometry"]["max_new_tokens"]))
        windows.append({"seed": seed, "finished": len(good),
                        "failed": len(result["failed"]) + len(malformed)
                        + result["hung"],
                        "sample": sample_finished(
                            good, seed,
                            int(cell["check"]["sample_requests"]))})
    serving.close()
    read = family.serve_readings(cell, fields,
                                 [w.pop("sample") for w in windows], controls)
    for window, entry in zip(windows, read):
        yield {**window, **entry}
