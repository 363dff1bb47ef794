"""One run of one cell: set-up, the measured window, the check, the metrics.

The window drives the serving program's own entry,
``ServingPlane.generate(tokens, n_tokens)``, back to back: one client,
one batch of ``batch`` requests per call, a fresh prompt batch per call
(made from the seed before the window and placed with the plane's batch
sharding).  The window holds the calls that start before ``seconds`` have
passed, from the first call's start to the last call's return.

With ``trace`` the same window runs under the profiler, with host
annotations from the benchmark's side around each program the plane
dispatches (``prefill``, ``decode.<rung>``, ``catchup``), around each
call (``call``) and around the harness's own work between calls
(``prompt``); ``trace.py`` reduces the trace.

After each call the harness, as the client, reads the call's answer: the
tokens served and fed back, and whether the logits of the served tokens
are finite.  Only those are kept; the call's full-vocabulary logits are
dropped before the next call, so the host holds no more at the end of the
window than at its start.
"""

from __future__ import annotations

import contextlib
import gc
import math
import statistics
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import check, costs, families, weights
from .spec import Cell

MIN_CALL_S = 0.2          # no call is faster: bounds the prompts made
WARMUP_TOKENS = 3         # the warm-up call: both rungs, both switches
TRACE_DIR = ".bench_traces"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Call:
    start: float
    end: float
    prefill_s: float
    decode_s: float
    rungs: List[str]
    catch_up_steps: int
    rss_bytes: int                  # the host process's, after the call


@dataclass
class Answer:
    """What a call served, as the client keeps it."""
    fed: np.ndarray                 # (B, n) tokens fed back
    served: np.ndarray              # (B, n + 1) tokens served
    finite: np.ndarray              # (B,) the served tokens' logits finite
    rungs: List[str]                # the rung the program says served each
    catch_up_steps: int             # decode steps it says it replayed


def answer(res) -> Answer:
    """The client's reading of one ``ServeResult``: the greedy tokens
    (``res.tokens``) after the accurate prefill's.  An argmax lands on a
    NaN or an infinity where a row has one, so the logit of the served
    token is finite only where the row's are."""
    first = res.prefill_logits.argmax(-1).astype(np.int32)       # (B,)
    tokens = res.tokens                                          # (n, B)
    finite = np.isfinite(np.take_along_axis(
        res.logits, tokens[..., None], axis=-1)[..., 0]).all(axis=0)
    finite &= np.isfinite(res.prefill_logits[np.arange(first.size), first])
    return Answer(fed=np.ascontiguousarray(res.inputs.T),
                  served=np.concatenate([first[None], tokens]).T.copy(),
                  finite=finite, rungs=list(res.rungs),
                  catch_up_steps=int(res.catch_up_steps))


def rss_bytes() -> int:
    """Resident set of this process (0 where ``/proc`` has none)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * 4096
    except OSError:
        return 0


@dataclass
class Step:
    """One program the plane dispatched in the traced window."""
    kind: str             # prefill | decode
    rung: str
    pos: int              # 0-based position of the token a decode step feeds
    catchup: bool


@dataclass
class Record:
    """What a run measured; each metric reader reads from it."""
    cell: Cell
    arch: object                            # the family's Arch
    device_kind: str
    setup_s: float
    window_s: float
    calls: List[Call]
    trace: Optional[object] = None          # trace.Reading of a traced run

    @property
    def peaks(self) -> dict:
        return costs.load_peaks(self.device_kind)

    @property
    def batch(self) -> int:
        return int(self.cell.traffic["batch"])

    @property
    def prompt_len(self) -> int:
        return int(self.cell.traffic["prompt_len"])

    @property
    def new_tokens(self) -> int:
        return int(self.cell.traffic["new_tokens"])

    @property
    def serving(self) -> dict:
        return self.cell.config["serving"]

    def decode_ms(self, rung: str) -> Optional[float]:
        """Median device time of one decode step of ``rung`` (served and
        catch-up steps alike) in the traced window."""
        times = self.trace and self.trace.step_times("decode", rung)
        return statistics.median(times) * 1e3 if times else None

    def request_ms(self, q: float) -> float:
        """The ``q``-th percentile, over every request of the window, of
        its latency: its call's return less its call's start (a call
        serves its batch together)."""
        per_request = np.repeat([(c.end - c.start) * 1e3 for c in self.calls],
                                self.batch)
        return float(np.percentile(per_request, q))


def check_rungs(plane, serving: dict) -> None:
    """The rungs the program built are the ones the file states."""
    for rung, want in (("accurate", serving["accurate"]),
                       ("fast", serving["fast"])):
        c = plane.models[rung].cfg
        kv = c.kv_cache_dtype or c.dtype
        got = {"sliding_window": c.sliding_window, "kv_cache_dtype": kv}
        if got != want:
            raise ValueError(f"{rung} rung runs {got}, the file states {want}")


def prompts(seed: int, n: int, batch: int, prompt_len: int,
            vocab: int, stream: int = 0) -> List[np.ndarray]:
    """Prompt batch ``i`` of the seed (token ids uniform over the
    vocabulary); ``stream`` 1 is the warm-up's.  Batch ``i`` does not
    depend on ``n``."""
    return [np.random.default_rng([int(seed), 2 + stream, i]).integers(
        0, vocab, size=(batch, prompt_len), dtype=np.int32) for i in range(n)]


@contextlib.contextmanager
def _no_annotation(name):
    yield


def module_name(compiled) -> str:
    """The XLA module name of a compiled program, as the trace names its
    runs."""
    return compiled.as_text().split(",", 1)[0].split()[-1]


class CompileCount:
    """Counts the executables jax builds (compiled or loaded from the
    persistent cache) while it is listening."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1


class Tracer:
    """Host annotations around what the plane dispatches, and the order
    of dispatch, for the traced window.  A decode step whose token was
    fed before in the same call replays it: a catch-up step."""

    def __init__(self, plane, prompt_len: int):
        import jax

        self.TA = jax.profiler.TraceAnnotation
        self.steps: List[Step] = []
        self.prompt_len = prompt_len
        self._seen: Dict[int, object] = {}
        self._pos: Dict[str, int] = {}
        for rung, fn in list(plane.prefill_fns.items()):
            plane.prefill_fns[rung] = self._wrap_prefill(rung, fn)
        for rung, fn in list(plane.step_fns.items()):
            plane.step_fns[rung] = self._wrap_decode(rung, fn)

    def new_call(self) -> None:
        self._seen.clear()
        self._pos = {}

    def _wrap_prefill(self, rung, fn):
        def wrapped(*args):
            self.steps.append(Step("prefill", rung, -1, False))
            self._pos[rung] = self.prompt_len
            with self.TA("prefill"):
                return fn(*args)
        return wrapped

    def _wrap_decode(self, rung, fn):
        def wrapped(params, state, token):
            catchup = id(token) in self._seen
            self._seen[id(token)] = token      # held: ids stay unique
            pos = self._pos[rung]
            self._pos[rung] = pos + 1
            self.steps.append(Step("decode", rung, pos, catchup))
            with self.TA("catchup" if catchup else f"decode.{rung}"):
                return fn(params, state, token)
        return wrapped


def build_plane(cell: Cell, devices, seed: int):
    """The serving plane the cell states, compiled, with the seed's
    weights; returns ``(plane, arch)``."""
    from repro.launch import serve

    serving = cell.config["serving"]
    t = cell.traffic
    arch = families.arch(cell.config)
    t0 = time.perf_counter()
    plane = serve.ServingPlane(
        arch.program_config(cell.config),
        mesh=serve.build_mesh(devices=devices),
        window=int(serving["fast"]["sliding_window"]),
        batch=int(t["batch"]), prompt_len=int(t["prompt_len"]),
        max_new=int(t["new_tokens"]))
    check_rungs(plane, serving)
    t1 = time.perf_counter()
    weights.install(plane, arch, seed)
    log(f"set-up: plane built in {t1 - t0!r} s (programs "
        f"{ {k: round(v, 3) for k, v in plane.compile_s.items()} }), "
        f"the seed's weights in {time.perf_counter() - t1!r} s")
    return plane, arch


@dataclass
class Served:
    """What the calls of a window served, for the check."""
    prompts: List[np.ndarray]       # per call (B, P)
    fed: List[np.ndarray]           # per call (B, n): tokens fed back
    served: List[np.ndarray]        # per call (B, n + 1): tokens served
    rungs: List[str]                # the stated rung of each decode step
    failed: int                     # requests with a non-finite logit or a
                                    # fed token other than the one served
    off_schedule: int               # calls whose rungs or replayed steps
                                    # differ from the stated schedule


def collect(answers: List[Answer], host_prompts, traffic: dict) -> Served:
    """The window's answers against what the mix states."""
    rungs = check.stated_rungs(traffic)
    replayed = check.replayed(rungs)
    failed = off = 0
    for a in answers:
        bad = ~a.finite | (a.fed != a.served[:, :-1]).any(axis=1)
        failed += int(bad.sum())
        off += int(a.rungs != rungs or a.catch_up_steps != replayed)
    return Served(prompts=host_prompts[:len(answers)],
                  fed=[a.fed for a in answers],
                  served=[a.served for a in answers], rungs=rungs,
                  failed=failed, off_schedule=off)


def compare(cell: Cell, arch, seed: int, got: Served, *,
            control: bool = False) -> Dict[str, float]:
    """The numbers compared, over the seed's sample of the requests
    served (see ``check.py``)."""
    b = int(cell.traffic["batch"])
    n = int(cell.traffic["new_tokens"])
    pick = check.sample(seed, len(got.fed) * b, n + 1)
    rows = [(i // b, i % b) for i in pick]
    log(f"sampled {len(rows)} requests, {len(rows) * (n + 1)} served tokens")
    return check.readings(
        arch, cell.config["serving"], seed,
        np.stack([got.prompts[c][r] for c, r in rows]),
        np.stack([got.fed[c][r] for c, r in rows]),
        np.stack([got.served[c][r] for c, r in rows]), got.rungs,
        control=control)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, t_process: float, root: Path,
             keep_trace: bool = False) -> tuple:
    """Run the cell once; returns ``(result, compared)`` where ``result``
    is the last line's object without ``check`` and ``compared`` holds
    each number compared with its limit.  A trace is written under
    ``root`` and removed once read, unless ``keep_trace``."""
    import jax

    from repro.launch.cache import use_compile_cache

    from . import trace as trace_mod

    cache_dir = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {cache_dir}")
    compiles = CompileCount()
    dev = devices[0]
    t = cell.traffic
    b, p, n = int(t["batch"]), int(t["prompt_len"]), int(t["new_tokens"])

    log(f"set-up: client ready at {time.perf_counter() - t_process!r} s")
    plane, arch = build_plane(cell, devices, seed)
    t_phase = time.perf_counter()
    n_calls = math.ceil(seconds / MIN_CALL_S) + 1
    host_prompts = prompts(seed, n_calls, b, p, arch.vocab)
    batches = [jax.device_put(x, plane.tokens_sh) for x in host_prompts]
    warm = jax.device_put(prompts(seed, 1, b, p, arch.vocab, stream=1)[0],
                          plane.tokens_sh)
    jax.block_until_ready(batches)
    t_warm = time.perf_counter()
    plane.generate(warm, WARMUP_TOKENS)
    log(f"set-up: {n_calls} prompt batches placed in {t_warm - t_phase!r} s, "
        f"warm-up call {time.perf_counter() - t_warm!r} s")
    modules = {module_name(f): kind for kind, fns in
               (("prefill", plane.prefill_fns), ("decode", plane.step_fns))
               for f in fns.values()} if trace else {}
    tracer = Tracer(plane, p) if trace else None
    annotate = tracer.TA if trace else _no_annotation
    if trace:
        plane.generate(warm, WARMUP_TOKENS)     # the wrappers, warmed
        tracer.steps.clear()
        trace_dir = root / TRACE_DIR / f"{cell.name}.{seed}"
        trace_mod.start(trace_dir)
    compiled_before = compiles.n
    setup_s = time.perf_counter() - t_process

    calls, answers = [], []
    t0 = time.perf_counter()
    with annotate("window"):
        for i in range(n_calls):
            with annotate("prompt"):            # the harness between calls
                start = time.perf_counter()
                if start - t0 >= seconds:
                    break
                if tracer:
                    tracer.new_call()
            with annotate("call"):
                res = plane.generate(batches[i], n)
            end = time.perf_counter()
            with annotate("prompt"):
                answers.append(answer(res))
                call = Call(start, end, res.prefill_s, res.decode_s,
                            list(res.rungs), res.catch_up_steps, 0)
                del res
                call.rss_bytes = rss_bytes()
                calls.append(call)
    window_s = calls[-1].end - t0
    reading = None
    if trace:
        reading = trace_mod.stop_and_read(trace_dir, modules, tracer.steps,
                                          n_devices=len(devices),
                                          keep=keep_trace)
    in_window = compiles.n - compiled_before
    log(f"window: {len(calls)} calls of {b} requests in {window_s!r} s; "
        f"{len(calls) * b} request latencies; compiles in the window: "
        f"{in_window}")
    log("calls (ms; wall/prefill/decode): " + " ".join(
        f"{(c.end - c.start) * 1e3:.0f}/{c.prefill_s * 1e3:.0f}/"
        f"{c.decode_s * 1e3:.0f}" for c in calls))
    log("host rss after each call (MiB): " + " ".join(
        f"{c.rss_bytes / 2**20:.0f}" for c in calls))
    # the runtime holds each program's temporaries in a reservation that
    # peak_bytes_in_use leaves out; the peak occupancy is both together
    stats = [d.memory_stats() or {} for d in devices]
    memory_peak = max(m.get("peak_bytes_in_use", 0)
                      + m.get("peak_bytes_reserved", 0) for m in stats)
    log(f"device memory: {stats[0]}")

    # what the window served, then the program's state freed
    got = collect(answers, host_prompts, t)
    del answers, batches, plane
    gc.collect()
    log(f"failed {got.failed}; calls off the stated schedule: "
        f"{got.off_schedule}")
    t_check = time.perf_counter()
    compared = {"failed": {"value": got.failed, "limit": 0},
                "off_schedule": {"value": got.off_schedule, "limit": 0}}
    compared.update({k: {"value": v, "limit": cell.limits[k]["limit"]}
                     for k, v in sorted(compare(cell, arch, seed,
                                                got).items())})
    log(f"reference check: {time.perf_counter() - t_check!r} s")
    correct = all(c["value"] <= c["limit"] for c in compared.values())

    record = Record(cell=cell, arch=arch, device_kind=dev.device_kind,
                    setup_s=setup_s, window_s=window_s, calls=calls,
                    trace=reading)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.read(record)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": len(calls) * b,
              "failed": got.failed, "metrics": metrics, "device": device}
    if reading is not None:
        device.update(busy_s=reading.busy_s, window_s=reading.window_s)
        result["breakdown"] = reading.breakdown()
    return result, compared
