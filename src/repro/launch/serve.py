"""Serving launcher with Compass configuration switching.

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b

runs the full-width model on every device the process has, on a
``1 x N`` ("data", "model") mesh (``1x1`` on one chip).  A CPU rehearsal
of a sharded mesh forces host devices explicitly:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \\
        --reduced --host-devices 8 --mesh 2x4 --batch 4 --prompt-len 32 \\
        --window 16

Demonstrates the paper's mechanism at the MODEL level: two serving
configurations of the same architecture (accurate = full attention / bf16
KV; fast = sliding-window / int8 KV) are compiled side by side against the
SAME weights, a seeded batch is prefilled, and the driver decodes tokens
while a synthetic queue-depth schedule switches the active executable — the
production-plane analogue of the paper's <10 ms pipeline rerouting (weights
stay resident; only the compiled step changes).  A rung that becomes active
first replays, through its own decode step, the tokens it missed, so every
rung's cache and position always cover the whole sequence.
"""

from __future__ import annotations

import argparse
import dataclasses
import itertools
import os
import time
from typing import Dict, List, Optional, Sequence

import ml_dtypes
import numpy as np

from .spans import Recorder, Span, seconds

RUNGS = ("accurate", "fast")
_SWITCH_DEPTH = 5     # queue depth above which the fast rung serves
SEED = 0              # parameters from PRNGKey(SEED), prompt from SEED + 1


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="serving launcher")
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU rehearsal only: force N host devices")
    ap.add_argument("--mesh", default="",
                    help="data x model mesh, e.g. 2x4 (default 1 x devices)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--window", type=int, default=256,
                    help="sliding window of the fast serving config")
    return ap.parse_args(argv)


def queue_depth(i: int, n_tokens: int) -> int:
    """Synthetic queue pressure: a spike over the middle third."""
    return 10 if n_tokens // 3 <= i < 2 * n_tokens // 3 else 0


def rung_schedule(n_tokens: int) -> List[str]:
    """The rung that serves each decode step under :func:`queue_depth`."""
    return ["fast" if queue_depth(i, n_tokens) > _SWITCH_DEPTH else "accurate"
            for i in range(n_tokens)]


def rung_configs(base, window: int) -> Dict[str, object]:
    """accurate = the architecture as published; fast = sliding window
    plus int8 KV (attention-free archs keep two identical rungs so the
    switching path still runs)."""
    if base.family == "ssm":
        return {"accurate": base, "fast": base}
    return {
        "accurate": base,
        "fast": dataclasses.replace(
            base, sliding_window=window,
            kv_cache_dtype=("int8" if base.family in ("dense", "hybrid", "moe")
                            else "")),
    }


@dataclasses.dataclass
class ServeResult:
    prefill_logits: np.ndarray      # (B, V) accurate rung, last prompt position
    inputs: np.ndarray              # (n, B) token fed to each decode step
    logits: np.ndarray              # (n, B, V) logits of each decode step
    rungs: List[str]                # rung that served each decode step
    spans: List[Span]               # the call's spans (see ``generate``)
    counters: Dict[str, int]        # host_bytes, catch_up_steps,
                                    # handoff_overlapped; MoE models:
                                    # moe_held_assignments

    def _first(self, name: str) -> Span:
        return next(s for s in self.spans if s.name == name)

    @property
    def prefill_s(self) -> float:
        """Both rungs' prefill: from the first dispatch until the device
        has done them."""
        return seconds(self._first("serve.prefill"),
                       self._first("serve.prefill.wait"))

    @property
    def decode_s(self) -> float:
        """Every decode step: from the first dispatch until the device has
        done them (the logits' hand-off runs inside that interval)."""
        return seconds(self._first("serve.decode"),
                       self._first("serve.decode.wait"))

    @property
    def switches(self) -> List[tuple]:
        """(step, from, to, queue depth) of each rung switch."""
        return [(s.attrs["step"], s.attrs["frm"], s.attrs["to"],
                 s.attrs["depth"]) for s in self.spans
                if s.name == "serve.switch"]

    @property
    def catch_up_steps(self) -> int:
        """Decode steps replayed at switches."""
        return self.counters["catch_up_steps"]

    @property
    def tokens(self) -> np.ndarray:
        """(n, B) greedy tokens generated by the decode steps."""
        return self.logits.argmax(-1).astype(np.int32)


def widen(x: np.ndarray, out: np.ndarray) -> None:
    """Write ``x`` into the float32 array ``out``, exactly.  A bfloat16
    value's bits are the high half of the same float32's, so they are
    shifted into place (zeros, infinities, NaN payloads and subnormals
    included); any other dtype is copied."""
    if x.dtype == ml_dtypes.bfloat16:
        np.left_shift(x.view(np.uint16), 16, out=out.view(np.uint32),
                      dtype=np.uint32)
    else:
        np.copyto(out, x)


def rung_programs(model, planner, *, batch: int, prompt_len: int,
                  max_new: int) -> Dict[str, tuple]:
    """The jitted prefill and decode step of one rung, with the planner's
    parameter, batch and cache shardings, each paired with the abstract
    arguments it compiles for: ``{"prefill": (jitted, args), "decode":
    (jitted, args)}``.  Call under ``jax.set_mesh(planner.mesh)``."""
    import jax
    import jax.numpy as jnp

    from ..sharding.planner import state_logical_axes

    param_sh = planner.param_shardings(model)
    params_abs = model.abstract_params()
    tok_abs = jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32)
    step_abs = jax.ShapeDtypeStruct((batch,), jnp.int32)
    cache_len = model.cache_len_for(prompt_len + max_new)

    def prefill(p, t):
        return model.prefill(p, {"tokens": t}, cache_len=cache_len)

    state_abs = jax.eval_shape(prefill, params_abs, tok_abs)[1]
    state_sh = planner.tree_shardings(state_abs,
                                      state_logical_axes(state_abs))
    return {
        "prefill": (jax.jit(prefill,
                            in_shardings=(param_sh,
                                          planner.batch_spec(tok_abs.shape)),
                            out_shardings=(None, state_sh)),
                    (params_abs, tok_abs)),
        "decode": (jax.jit(model.decode_step,
                           in_shardings=(param_sh, state_sh,
                                         planner.batch_spec(step_abs.shape)),
                           out_shardings=(None, state_sh),
                           donate_argnums=(1,)),
                   (params_abs, state_abs, step_abs)),
    }


class ServingPlane:
    """Two serving rungs over one set of weights on one mesh.

    Parameters are generated on the devices from ``SEED`` (nothing is
    loaded) with the planner's shardings; each rung has a compiled prefill
    and a compiled decode step (:func:`rung_programs`) whose cache
    shardings come from the same planner.  ``compile_s`` holds the seconds
    spent compiling each."""

    def __init__(self, cfg, *, mesh, window: int, batch: int,
                 prompt_len: int, max_new: int):
        import jax
        import jax.numpy as jnp

        from ..models.registry import build_model
        from ..sharding.planner import ShardingPlanner

        self.cfg, self.mesh = cfg, mesh
        self.models = {k: build_model(c)
                       for k, c in rung_configs(cfg, window).items()}
        acc = self.models["accurate"]
        planner = ShardingPlanner(mesh, fsdp=False, context="serve")
        self.param_sh = planner.param_shardings(acc)
        self.compile_s: Dict[str, float] = {}
        self._calls = itertools.count()       # call ids of ``generate``
        self.tokens_sh = planner.batch_spec((batch, prompt_len))
        self.step_tok_sh = planner.batch_spec((batch,))
        key_abs = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                       sharding=planner.replicated())

        with jax.set_mesh(mesh):
            init = self._compile(
                "init", jax.jit(acc.init, out_shardings=self.param_sh),
                (key_abs,))
            self.prefill_fns, self.step_fns = {}, {}
            for name, m in self.models.items():
                progs = rung_programs(m, planner, batch=batch,
                                      prompt_len=prompt_len, max_new=max_new)
                self.prefill_fns[name] = self._compile(
                    f"prefill_{name}", *progs["prefill"])
                self.step_fns[name] = self._compile(
                    f"decode_{name}", *progs["decode"])
        self.params = init(jax.device_put(jax.random.PRNGKey(SEED),
                                          planner.replicated()))

    def _compile(self, name, jitted, abstract_args):
        t0 = time.perf_counter()
        compiled = jitted.lower(*abstract_args).compile()
        self.compile_s[name] = time.perf_counter() - t0
        return compiled

    def prompt(self, batch: int, prompt_len: int):
        """The seeded prompt batch, placed with the batch sharding."""
        import jax

        tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                    (batch, prompt_len), 0,
                                    self.cfg.vocab_size)
        return jax.device_put(tokens, self.tokens_sh)

    def generate(self, tokens, n_tokens: int) -> ServeResult:
        """Prefill every rung on ``tokens``, then greedily decode
        ``n_tokens`` steps under :func:`rung_schedule`, handing each
        step's logits to the host while the device runs later steps.

        Each served step's logits and each fed token start their copy to
        the host as they are dispatched.  The host drains them in step
        order: after each served step's dispatch it takes every earlier
        step the device has done, and after the last dispatch the rest.
        Draining a step waits for its copy, widens its logits into the
        float32 result (:func:`widen`) and drops the device array, so the
        hand-off runs beside the decode steps still on the device rather
        than after them.  Nothing in the hand-off compiles.

        The call's spans, all under one ``serve.call`` with the call's id:
        ``serve.prefill`` (``rung``) around each rung's prefill dispatch
        and ``serve.prefill.wait`` until the device has done them;
        ``serve.decode`` (``rung``, ``step``, ``pos``) around each served
        step, from its dispatch through the greedy pick; ``serve.switch``
        (``step``, ``frm``, ``to``, ``depth``, ``replayed``) around a
        switch and the ``serve.catchup`` steps (``rung``, ``pos``) that
        replay the tokens the new rung missed; ``serve.handoff`` around
        each drain, between served steps and inside ``serve.decode.wait``
        (from the last dispatch until the device has done every step),
        with a ``serve.handoff.transfer`` (``bytes``) around each wait for
        an array's copy to the host and a ``serve.handoff.widen`` around
        each logit array's widening; for a MoE model ``serve.moe.load``
        (``bytes``) around reading the decode states' expert counters
        after the wait.  Counters: ``host_bytes`` copied to the host,
        ``catch_up_steps``, ``handoff_overlapped``: decode logit arrays
        widened while the device still had decode steps to run;
        ``moe_held_assignments`` (MoE): the (token, expert) assignments of
        every decode step of the call, catch-up steps included, that
        landed on the experts this chip holds, summed over layers."""
        import jax
        import jax.numpy as jnp

        rec = Recorder(next(self._calls))
        with rec.span("serve.call", call=rec.call):
            schedule = rung_schedule(n_tokens)
            p = tokens.shape[1]
            states, first = {}, None
            for name in RUNGS:
                with rec.span("serve.prefill", rung=name):
                    last, states[name] = self.prefill_fns[name](self.params,
                                                                tokens)
                if name == "accurate":
                    first = last
                    first.copy_to_host_async()
            with rec.span("serve.prefill.wait"):
                jax.block_until_ready(states)

            def greedy(logits):
                return jax.device_put(
                    jnp.argmax(logits, -1).astype(jnp.int32),
                    self.step_tok_sh)

            def to_host(x):
                with rec.span("serve.handoff.transfer", bytes=x.nbytes):
                    out = np.asarray(x)
                rec.count("host_bytes", x.nbytes)
                return out

            def widened(x, out):
                host = to_host(x)
                with rec.span("serve.handoff.widen"):
                    widen(host, out)

            fed: List = []                   # decode inputs, in order
            logits_steps = []                # served steps' device logits
            prefill_logits = np.empty(first.shape, np.float32)
            logits = np.empty((n_tokens,) + first.shape, np.float32)
            host_fed, drained, overlapped = [], 0, 0

            def drain(wait: bool) -> None:
                """Hand the served steps' inputs and logits to the host in
                step order: those the device has done, or with ``wait``
                all of them."""
                nonlocal drained, overlapped
                while drained < len(logits_steps) and (
                        wait or logits_steps[drained].is_ready()):
                    host_fed.append(to_host(fed[drained]))
                    widened(logits_steps[drained], logits[drained])
                    logits_steps[drained] = None   # its HBM may go
                    drained += 1
                    overlapped += not tok.is_ready()

            done = {name: 0 for name in RUNGS}
            tok = greedy(first)
            active = schedule[0]
            for i, want in enumerate(schedule):
                if want != active:
                    # replay the tokens this rung missed while the other
                    # one served
                    missed = range(done[want], len(fed))
                    with rec.span("serve.switch", step=i, frm=active,
                                  to=want, depth=queue_depth(i, n_tokens),
                                  replayed=len(missed)):
                        for k in missed:
                            with rec.span("serve.catchup", rung=want,
                                          pos=p + k):
                                _, states[want] = self.step_fns[want](
                                    self.params, states[want], fed[k])
                    active = want
                fed.append(tok)
                tok.copy_to_host_async()
                with rec.span("serve.decode", rung=active, step=i,
                              pos=p + i):
                    step_logits, states[active] = self.step_fns[active](
                        self.params, states[active], tok)
                    step_logits.copy_to_host_async()
                    tok = greedy(step_logits)
                done[active] = len(fed)
                logits_steps.append(step_logits)
                if logits_steps[drained].is_ready():
                    with rec.span("serve.handoff"):
                        drain(wait=False)
            rec.count("catch_up_steps", sum(s.name == "serve.catchup"
                                            for s in rec.spans))

            with rec.span("serve.decode.wait"):
                with rec.span("serve.handoff"):
                    widened(first, prefill_logits)
                    drain(wait=True)
                rec.count("handoff_overlapped", overlapped)
                jax.block_until_ready(tok)
            if self.cfg.family == "moe":
                counts = [seg["held"] for name in RUNGS
                          for seg in states[name]["segments"]]
                with rec.span("serve.moe.load",
                              bytes=sum(c.nbytes for c in counts)):
                    rec.count("moe_held_assignments",
                              sum(int(np.asarray(c).sum()) for c in counts))

            res = ServeResult(prefill_logits=prefill_logits,
                              inputs=np.stack(host_fed), logits=logits,
                              rungs=schedule, spans=rec.spans,
                              counters=rec.counters)
        return res


def reference_forwards(cfg, window: int) -> Dict[str, object]:
    """Each rung's teacher-forced reference: its attention pattern's
    ``forward`` at float32 with full-precision KV, jitted as
    ``fn(params, tokens) -> (B, S, V) logits``.  Parameters are float32 in
    every rung, so one set serves all.  Call under the highest matmul
    precision for a float32 reference on a TPU."""
    import jax

    from ..models.registry import build_model

    out = {}
    for name, c in rung_configs(dataclasses.replace(cfg, dtype="float32"),
                                window).items():
        m = build_model(dataclasses.replace(c, kv_cache_dtype=""))
        out[name] = jax.jit(lambda p, t, m=m: m.forward(p, {"tokens": t})[0])
    return out


def logit_agreement(ref, got) -> tuple:
    """(max |diff| / max |ref|, correlation) of two logit arrays."""
    ref = np.asarray(ref, np.float64).ravel()
    got = np.asarray(got, np.float64).ravel()
    rel = float(np.abs(ref - got).max() / max(np.abs(ref).max(), 1e-30))
    return rel, float(np.corrcoef(ref, got)[0, 1])


def rung_agreement(forwards, params, prompt, res: ServeResult,
                   rows: int = 2) -> Dict[str, Dict[str, tuple]]:
    """Hold the logits of ``res`` to the teacher-forced ``forwards`` (see
    :func:`reference_forwards`) over ``prompt`` and every decode input, on
    the first ``rows`` rows.  Decode step i sits at position P + i of the
    forward, the accurate prefill at P - 1.  Returns ``{served rung:
    {reference rung: (max_rel, min_corr, mean_corr)}}`` over the steps
    that rung served (the accurate rung's include its prefill)."""
    p = prompt.shape[1]
    seq = np.concatenate([np.asarray(prompt)[:rows],
                          res.inputs[:, :rows].T], axis=1)
    refs = {name: np.asarray(fn(params, seq), np.float32)
            for name, fn in forwards.items()}
    out: Dict[str, Dict[str, tuple]] = {}
    for rung in RUNGS:
        pairs = [(p + i, res.logits[i, :rows])
                 for i, r in enumerate(res.rungs) if r == rung]
        if rung == "accurate":
            pairs.insert(0, (p - 1, res.prefill_logits[:rows]))
        out[rung] = {}
        for ref_rung, ref in refs.items():
            agree = [logit_agreement(ref[:, pos], got) for pos, got in pairs]
            out[rung][ref_rung] = (max(a for a, _ in agree),
                                   min(c for _, c in agree),
                                   float(np.mean([c for _, c in agree])))
    return out


def device_info() -> Dict[str, object]:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def build_mesh(spec: str = "", devices=None):
    """("data", "model") mesh over ``devices`` (default: all of them);
    ``spec`` "DxM" fixes its shape, else it is 1 x len(devices)."""
    import jax

    from .mesh import make_mesh

    devices = list(jax.devices() if devices is None else devices)
    dims = ([int(x) for x in spec.split("x")] if spec
            else [1, len(devices)])
    if len(dims) != 2 or dims[0] * dims[1] != len(devices):
        raise SystemExit(f"mesh {spec or dims} does not cover the "
                         f"{len(devices)} devices")
    return make_mesh(dims, ("data", "model"), devices=devices)


def run(args: argparse.Namespace):
    """Build the serving plane that ``args`` describes, serve the seeded
    prompt batch, print what happened; returns (plane, result)."""
    import repro.configs  # noqa: F401
    from ..configs.reduced import reduced_config
    from ..models.registry import get_config
    from .cache import use_compile_cache

    use_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = build_mesh(args.mesh)
    dev = device_info()
    print(f"devices: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']} mesh={dict(mesh.shape)}")
    print(f"{cfg.arch_id}{' (reduced)' if args.reduced else ''}: "
          f"{cfg.num_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}; batch {args.batch}, "
          f"prompt {args.prompt_len}, {args.tokens} new tokens")
    plane = ServingPlane(cfg, mesh=mesh, window=args.window,
                         batch=args.batch, prompt_len=args.prompt_len,
                         max_new=args.tokens)
    for name, m in plane.models.items():
        print(f"compiled serving config '{name}' "
              f"(window={m.cfg.sliding_window or 'full'}, "
              f"kv={m.cfg.kv_cache_dtype or m.cfg.dtype}): prefill "
              f"{plane.compile_s[f'prefill_{name}']:.1f}s, decode "
              f"{plane.compile_s[f'decode_{name}']:.1f}s")
    res = plane.generate(plane.prompt(args.batch, args.prompt_len),
                         args.tokens)
    for i, src, dst, depth in res.switches:
        print(f"  token {i:3d}: switch {src} -> {dst} (queue depth {depth})")
    print(f"prefill {args.batch} x {args.prompt_len} tokens in "
          f"{res.prefill_s:.3f}s; decoded {args.tokens} tokens x batch "
          f"{args.batch} ({res.catch_up_steps} catch-up steps) in "
          f"{res.decode_s:.3f}s on {dev['count']} {dev['kind']}")
    print(call_line(res))
    return plane, res


def call_line(res: ServeResult) -> str:
    """One line per call, read from its spans: prefill, decode and
    hand-off times (the hand-off runs inside decode's interval), the
    hand-off's waits for the copies and its widening, how many steps were
    widened while decode still ran, the bytes handed to the host, and for
    a MoE model the assignments its held experts served."""
    ms = {name: 1e3 * sum(s.seconds for s in res.spans if s.name == name)
          for name in ("serve.handoff", "serve.handoff.transfer",
                       "serve.handoff.widen")}
    mb = res.counters["host_bytes"] / 1e6
    return (f"call {res.spans[0].call}: prefill {res.prefill_s * 1e3:.1f} "
            f"ms, decode {res.decode_s * 1e3:.1f} ms, hand-off "
            f"{ms['serve.handoff']:.1f} ms (transfers "
            f"{ms['serve.handoff.transfer']:.1f} ms, widening "
            f"{ms['serve.handoff.widen']:.1f} ms; "
            f"{res.counters['handoff_overlapped']} of {len(res.rungs)} "
            f"steps while decode ran); {mb:.2f} MB to the host"
            + (f"; moe_held_assignments {res.counters['moe_held_assignments']}"
               if "moe_held_assignments" in res.counters else ""))


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices}")
    run(args)


if __name__ == "__main__":
    main()
