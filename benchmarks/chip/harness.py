"""One run of one cell: set-up, a measured window, a drain, the check.

A cell (``workloads/<name>.json``) names a configuration
(``configs/<name>.json``), a traffic mix (``traffic/<name>.json``), the
rate at which sessions start, the lead before the window opens and what
the check samples.  Metrics are files of their own (``metrics/<name>.py``,
each with ``read(run)``); which of them a cell reports comes from
``BENCHMARK.json``.  Adding a configuration, a mix, a cell or a metric adds
files and touches none of these.

The system under test is ``PagedServingSession`` built by the launcher's
own ``_build_session`` for ``--cache paged --prefix-cache trie
--prefill-budget B``; the benchmark drives its ``add_request`` / ``step`` /
``finish`` and wraps each call in a profiler span of the same name.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import heapq
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

import numpy as np

import traffic as traffic_mod

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
TRACE_DIR = HERE / ".trace"
# Token streams of the warm-up prompts, apart from the plan's sessions.
WARMUP_STREAM = 1 << 28


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def load_json(kind: str, name: str, root: pathlib.Path = HERE) -> dict:
    return json.loads((root / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str, root: pathlib.Path = HERE):
    path = root / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports: ``end_to_end`` for a plain run,
    ``per_layer`` for a traced one, each unless its ``workloads`` leave the
    cell out."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# --------------------------------------------------------------------------- #
# requests and the open-loop load
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class Request:
    session: int
    turn: int
    due: float  # seconds on the run clock
    prompt: list[int]
    out_len: int
    known: int  # prompt tokens an ideal prefix cache already holds
    rid: int | None = None
    admitted: float | None = None
    tokens: list[int] = dataclasses.field(default_factory=list)
    times: list[float] = dataclasses.field(default_factory=list)
    done: float | None = None


class Compiles:
    """Backend compiles (persistent-cache hits included) and cache reads,
    through jax.monitoring."""

    def __init__(self):
        import jax

        self.times: list[float] = []
        self.names: list[str] = []
        self.read_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == BACKEND_COMPILE:
            self.times.append(time.monotonic())
            self.names.append(str(kw.get("fun_name", "?")))
        elif name == CACHE_READ:
            self.read_s += secs

    def between(self, t0: float, t1: float) -> list[str]:
        """Names of the programs compiled between two times."""
        return [n for t, n in zip(self.times, self.names) if t0 <= t < t1]


class OpenLoop:
    """Sends a plan's turns when due, admits them as room allows, steps the
    session and records every token with the time its step returned."""

    def __init__(self, sess, mix, plan, seed, vocab, sys_prompt, t0, stream=0):
        import jax

        self.annotate = jax.profiler.TraceAnnotation
        self.sess, self.mix, self.plan = sess, mix, plan
        self.seed, self.vocab, self.sys = seed, vocab, sys_prompt
        self.t0, self.stream = t0, stream
        self.next_session = 0
        self.followups: list[tuple[float, int, int, Request]] = []  # heap
        self.queue: collections.deque[Request] = collections.deque()
        self.live: dict[int, Request] = {}
        self.requests: list[Request] = []
        self.steps: list[tuple[float, float]] = []
        self.peak_live = 0  # the largest decode batch a step ran

    def now(self) -> float:
        return time.monotonic() - self.t0

    def _new_request(self, s, k, due, prev: Request | None) -> Request:
        turn = self.plan[s].turns[k]
        new = traffic_mod.turn_tokens(
            self.seed, self.stream + s, k, turn.new_tokens, self.vocab
        )
        if prev is None:
            history, known = list(self.sys), len(self.sys)
        else:
            history = prev.prompt + prev.tokens
            known = len(history) - 1  # the reply's last token never ran
        req = Request(s, k, due, history + new, turn.output_tokens, known)
        self.requests.append(req)
        return req

    def _send_due(self, now: float, send_until: float) -> None:
        while (
            self.next_session < len(self.plan)
            and self.plan[self.next_session].start_s <= now
            and self.plan[self.next_session].start_s < send_until
        ):
            s = self.next_session
            self.queue.append(self._new_request(s, 0, self.plan[s].start_s, None))
            self.next_session += 1
        while (
            self.followups
            and self.followups[0][0] <= now
            and self.followups[0][0] < send_until
        ):
            due, s, k, prev = heapq.heappop(self.followups)
            self.queue.append(self._new_request(s, k, due, prev))

    def _admit(self, now: float) -> None:
        while self.queue and len(self.sess.resident_rids()) < self.mix.max_batch:
            req = self.queue[0]
            with self.annotate("add_request"):
                rid = self.sess.add_request(req.prompt)
            if rid is None:
                return
            self.queue.popleft()
            req.rid, req.admitted = rid, now
            self.live[rid] = req

    def _step(self) -> None:
        self.peak_live = max(self.peak_live, len(self.sess.active))
        t_start = self.now()
        with self.annotate("step"):
            self.sess.step()
        t = self.now()
        self.steps.append((t_start, t))
        done = []
        for rid, req in self.live.items():
            out = self.sess.outputs[rid]
            new = out[len(req.tokens) : req.out_len]
            req.tokens.extend(int(x) for x in new)
            req.times.extend([t] * len(new))
            if len(req.tokens) >= req.out_len:
                done.append(rid)
        for rid in done:
            req = self.live.pop(rid)
            with self.annotate("finish"):
                self.sess.finish(rid)
            req.done = t
            if req.turn + 1 < len(self.plan[req.session].turns):
                due = t + self.mix.think_s
                heapq.heappush(
                    self.followups, (due, req.session, req.turn + 1, req)
                )

    def _next_due(self) -> float:
        nxt = math.inf
        if self.next_session < len(self.plan):
            nxt = self.plan[self.next_session].start_s
        if self.followups:
            nxt = min(nxt, self.followups[0][0])
        return nxt

    def busy(self) -> bool:
        return bool(self.queue or self.live)

    def waiting(self, since: float, sessions: int) -> bool:
        """Whether a request due at or after ``since`` has no token yet, or
        fewer than ``sessions`` sessions have a finished turn."""
        if any(r.due >= since and not r.times for r in self.requests):
            return True
        return len({r.session for r in self.requests if r.done is not None}) < sessions

    def run(self, until: float, send_until: float, drain_from: float | None = None,
            sessions: int = 0) -> None:
        """Serve until the run clock reaches ``until``.  With ``drain_from``,
        stop as soon as every request due since then has its first token (no
        metric reads a later token) and ``sessions`` sessions have a finished
        turn for the check to sample."""
        while True:
            now = self.now()
            if now >= until or (
                drain_from is not None and not self.waiting(drain_from, sessions)
            ):
                return
            self._send_due(now, send_until)
            self._admit(now)
            if self.live:
                self._step()
            else:
                wait = min(self._next_due(), until) - self.now()
                if wait > 0:
                    time.sleep(min(wait, 0.002))


# --------------------------------------------------------------------------- #
# the run record that metric readers see
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class RunRecord:
    conf: dict
    seconds: float
    setup_s: float
    t_open: float
    t_close: float
    requests: list[Request]
    steps: list[tuple[float, float]]
    counters_open: dict
    counters_close: dict
    peaks: dict
    trace: dict | None = None

    def in_window(self, t: float) -> bool:
        return self.t_open <= t < self.t_close

    def due_in_window(self) -> list[Request]:
        return [r for r in self.requests if self.in_window(r.due)]

    def window_tokens(self) -> int:
        return sum(self.in_window(t) for r in self.requests for t in r.times)

    def window_steps(self) -> int:
        return sum(self.in_window(t1) for _, t1 in self.steps)

    def window_work(self) -> dict:
        """What the window's tokens need, from the benchmark's own record:
        attention operations and bytes, and dense operations."""
        import flops

        att_ops = att_bytes = dense = 0
        per_tok = flops.dense_ops_per_token(self.conf)
        unembed = flops.unembed_ops(self.conf)
        for r in self.requests:
            for i, t in enumerate(r.times):
                if not self.in_window(t):
                    continue
                if i == 0:  # the prompt's last chunk ran in this step
                    n = len(r.prompt) - r.known
                    o, b = flops.prefill_attention(self.conf, r.known, n)
                    dense += n * per_tok + unembed
                else:  # token i-1 decoded at position len(prompt) + i - 1
                    o, b = flops.decode_attention(self.conf, [len(r.prompt) + i])
                    dense += per_tok + unembed
                att_ops += o
                att_bytes += b
        return {
            "attention_ops": att_ops,
            "attention_bytes": att_bytes,
            "dense_ops": dense,
        }


def first_token_waits(run: RunRecord) -> list[float]:
    """Milliseconds from due to first token of every request due in the
    window; one never served waits until the last step of the run."""
    end = max(t1 for _, t1 in run.steps)
    return [
        ((r.times[0] if r.times else end) - r.due) * 1e3
        for r in run.due_in_window()
    ]


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in [0, 100])."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no samples")
    k = max(0, min(len(xs) - 1, math.ceil(q / 100 * len(xs)) - 1))
    return xs[k]


# --------------------------------------------------------------------------- #
# set-up
# --------------------------------------------------------------------------- #


def build_session(model, params, conf, mix, interpret: bool):
    """The launcher's own session for ``--cache paged --prefix-cache trie
    --prefill-budget B`` at this config's serving sizes."""
    from repro.launch import serve

    sv = conf["serving"]
    argv = [
        "--cache", "paged",
        "--prefix-cache", "trie",
        "--prefill-budget", str(sv["prefill_budget"]),
        "--prefill-chunk", str(sv["prefill_chunk"]),
        "--page-size", str(sv["page_size"]),
        "--block-k", str(sv["block_k"]),
        "--num-pages", str(sv["num_pages"]),
        "--batch", str(mix.max_batch),
    ]
    if interpret:
        argv.append("--interpret")
    args = serve.build_parser().parse_args(argv)
    return serve._build_session(args, model.cfg, model, params)


def warm_tails(sess, conf, mix, seed, vocab, sys_prompt) -> None:
    """One prompt of each tail length 1 .. prefill_chunk after the system
    prompt, one token out each: every shape the last chunk of a chunked
    prefill can take compiles here and not in the window."""
    chunk = conf["serving"]["prefill_chunk"]
    plan = [
        traffic_mod.SessionPlan(i, 0.0, (traffic_mod.Turn(r, 1),))
        for i, r in enumerate(range(1, chunk + 1))
    ]
    drv = OpenLoop(sess, mix, plan, seed, vocab, sys_prompt, time.monotonic(),
                 stream=WARMUP_STREAM)
    drv._send_due(0.0, send_until=1.0)
    drv.run(until=math.inf, send_until=1.0, drain_from=0.0)


def decode_layouts(mix, plan, block_k: int, bucket: int) -> list[list[int]]:
    """Every decode batch the window can meet, as layouts of block counts.

    A decode step's programs depend on the live batch ``b`` and on the
    length of its work queue, padded to a multiple of ``bucket``: one item
    per ``block_k`` block a request attends, counted past the shared
    system-prompt blocks when they form a group, or over the whole context
    when they do not.  Each request of the plan attends between ``lo`` and
    ``hi`` blocks past the shared ones over its decode steps, so a batch of
    ``b`` holds a total ``T`` in ``[b*lo, b*hi]``.  For each ``b`` from 1
    to ``max_batch`` this lists one layout (``b`` counts in ``[lo, hi]``,
    summing to ``T``) for every padded length that either count can take,
    in rising ``T``, so that each request's count only grows."""
    sys_len = mix.system_prompt_tokens
    shared = sys_len // block_k
    lo, hi = math.inf, 0
    for p in plan:
        prompt = sys_len
        for t in p.turns:
            prompt += t.new_tokens
            if t.output_tokens > 1:  # decode steps run at kv P+1 .. P+out-1
                lo = min(lo, -(-(prompt + 1) // block_k) - shared)
                hi = max(hi, -(-(prompt + t.output_tokens - 1) // block_k) - shared)
            prompt += t.output_tokens
    # The warm-up's own requests start one token past the system prompt.
    lo = max(1, min(lo, -(-(sys_len + 2) // block_k) - shared))
    layouts = []
    for b in range(1, mix.max_batch + 1):
        first, last = b * lo, b * hi
        totals = {first, last}
        for skip in (0, b * shared):  # suffix queue, whole-context queue
            for k in range((first + skip) // bucket, (last + skip) // bucket + 1):
                totals.add((k + 1) * bucket - skip)  # the top of each length
        for total in sorted(t for t in totals if first <= t <= last):
            extra, lay = total - first, []
            for _ in range(b):
                add = min(extra, hi - lo)
                lay.append(lo + add)
                extra -= add
            layouts.append(lay)
    return layouts


def warm_decode(sess, layouts, block_k, seed, vocab, sys_prompt) -> int:
    """One decode step of the session at every layout of
    :func:`decode_layouts`, so each program the window's decode steps can
    use is compiled (or read from the cache) here and not in the window.

    For each batch size, that many requests of the system prompt and one
    token each are admitted through ``add_request`` (the trie adopts the
    system prompt, as it does for the plan's turns); before each step the
    page table of each request is grown to its count with the cache's own
    ``reserve``, whose rows are never read by a served request.  Returns
    the number of decode steps."""
    from itertools import groupby

    base = len(sys_prompt) // block_k * block_k
    steps = 0
    for b, group in groupby(layouts, key=len):
        rids = []
        for i in range(b):
            tok = traffic_mod.token_ids(seed, (2, b, i), 1, vocab)
            rids.append(sess.add_request(list(sys_prompt) + tok))
        while not all(sess.outputs.get(r) for r in rids):
            sess.step()
        for lay in group:
            for rid, blocks in zip(rids, lay):
                # The step's own token lands at this length + 1, in block
                # shared + blocks.
                want = base + (blocks - 1) * block_k
                have = sess.cache.seq_len(rid)
                if want > have:
                    sess.cache.reserve(rid, want - have)
            sess.step()
            steps += 1
        for rid in rids:
            sess.finish(rid)
    return steps


def fill_prefix(sess, sys_prompt) -> None:
    """Prefill the shared system prompt once, so the prefix cache holds it."""
    if not sys_prompt:
        return
    rid = sess.add_request(sys_prompt)
    while not sess.outputs.get(rid):
        sess.step()
    sess.finish(rid)


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


# --------------------------------------------------------------------------- #
# the check
# --------------------------------------------------------------------------- #


def sample_sequences(requests: list[Request], seed: int, k: int) -> list[dict]:
    """Finished sessions drawn from the seed, the longest always among them.

    A session's turns each extend the one before, so one sequence -- the
    last finished turn's prompt and reply -- holds every finished turn:
    each served token is checked at its own position in it."""
    by_session: dict[int, list[Request]] = {}
    for r in requests:
        if r.done is not None:
            by_session.setdefault(r.session, []).append(r)
    seqs = []
    for s, turns in sorted(by_session.items()):
        turns.sort(key=lambda r: r.turn)
        last = turns[-1]
        tokens = last.prompt + last.tokens
        positions, served = [], []
        for r in turns:
            if tokens[: len(r.prompt) + len(r.tokens)] != r.prompt + r.tokens:
                raise AssertionError(f"session {s}: turns do not extend each other")
            positions += range(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens))
            served += r.tokens
        seqs.append({"session": s, "tokens": tokens, "positions": positions,
                     "served": served})
    if not seqs:
        return []
    longest = max(range(len(seqs)), key=lambda i: len(seqs[i]["tokens"]))
    rest = [i for i in range(len(seqs)) if i != longest]
    rng = np.random.default_rng([int(seed) & (2**63 - 1), 7])
    pick = rng.permutation(len(rest))[: max(k - 1, 0)]
    return [seqs[longest]] + [seqs[rest[i]] for i in sorted(pick)]


def logit_gaps(family, params, conf, seqs, control: bool = False, pad_to: int = 0) -> dict:
    """Widest gap by which a served token's reference logit lies below the
    reference's best; with ``control``, the same for the token the int8
    control puts first at each of those positions."""
    out = {"logit_gap_max": 0.0, "checked_tokens": 0}
    if control:
        out["control_gap_max"] = 0.0
    for sq in seqs:
        ref = family.reference_logits(
            params, conf, sq["tokens"], sq["positions"], pad_to=pad_to
        )
        best = ref.max(axis=1)
        idx = np.arange(len(sq["served"]))
        gap = best - ref[idx, np.asarray(sq["served"])]
        out["logit_gap_max"] = max(out["logit_gap_max"], float(gap.max()))
        out["checked_tokens"] += len(sq["served"])
        if control:
            low = family.reference_logits(
                params, conf, sq["tokens"], sq["positions"], int8=True,
                pad_to=pad_to,
            )
            cg = best - ref[idx, low.argmax(axis=1)]
            out["control_gap_max"] = max(out["control_gap_max"], float(cg.max()))
    return out


def judge(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each compared number beside its limit, and whether all keep to them.

    Besides the cell's own limits, a window that compiled (or read from the
    cache) any program is not admitted: its limit is 0."""
    limits = {**limits, "window_compiles": 0}
    check = {name: {"value": readings[name], "limit": lim} for name, lim in limits.items()}
    return check, all(c["value"] <= c["limit"] for c in check.values())


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #


@dataclasses.dataclass
class Options:
    interpret: bool = False  # Pallas interpret mode (CPU tests only)
    require_chip: bool = True
    control: bool = False  # also read the int8 control (control.py)
    session_hook: object = None  # tests: break the timed path underneath
    root: pathlib.Path = HERE  # where configs/, traffic/, workloads/ live
    bench: dict | None = None  # BENCHMARK.json's content (default: read it)


def run_cell(cell_name: str, seed: int, seconds: float, trace: bool,
             t_start: float, opts: Options | None = None) -> tuple[dict, dict]:
    """One run: the result line's object, and what else the run read (the
    check's readings, compiles inside the window, set-up phases)."""
    import jax

    from repro.models.model_zoo import build_model
    from repro.runtime.compile_cache import enable_compile_cache

    opts = opts or Options()
    root = opts.root
    bench = opts.bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = load_json("workloads", cell_name, root)
    conf = load_json("configs", cell["config"], root)
    conf["name"] = cell["config"]
    mix = traffic_mod.load_mix(cell["traffic"], root)
    family = load_module("families", conf["family"])

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if opts.require_chip:
        if devices[0].platform != "tpu":
            raise SystemExit(f"no accelerator: jax found {devices[0].platform}")
        if len(devices) < cell.get("chips", 1):
            raise SystemExit(
                f"cell {cell_name} needs {cell.get('chips', 1)} chips; jax sees {len(devices)}"
            )
    used = devices[: cell.get("chips", 1)]
    import flops

    peaks = flops.peaks(devices[0].device_kind) if opts.require_chip else {}
    compiles = Compiles()
    phases = {}

    t = time.monotonic()
    cfg = family.program_config(conf)
    model = build_model(cfg)
    params = family.make_params(model, conf, seed)
    sess = build_session(model, params, conf, mix, opts.interpret)
    if opts.session_hook is not None:
        opts.session_hook(sess)
    phases["weights_s"] = time.monotonic() - t

    vocab = conf["vocab_size"]
    sys_prompt = traffic_mod.system_prompt(mix, seed, vocab)
    t = time.monotonic()
    fill_prefix(sess, sys_prompt)
    phases["prefix_fill_s"] = time.monotonic() - t

    lead = float(cell["lead_s"])
    plan = traffic_mod.plan_sessions(mix, float(cell["rate"]), lead + seconds)
    t = time.monotonic()
    warm_tails(sess, conf, mix, seed, vocab, sys_prompt)
    phases["warmup_s"] = time.monotonic() - t

    from repro.kernels.decode_schedule import DEFAULT_QUEUE_BUCKET

    block_k = conf["serving"]["block_k"]
    t = time.monotonic()
    layouts = decode_layouts(mix, plan, block_k, DEFAULT_QUEUE_BUCKET)
    phases["decode_warmup_steps"] = warm_decode(
        sess, layouts, block_k, seed, vocab, sys_prompt
    )
    phases["decode_warmup_s"] = time.monotonic() - t

    # The lead runs the cell's own schedule, the same for every seed, so
    # the window opens on a batch that is already full.
    t = time.monotonic()
    drv = OpenLoop(sess, mix, plan, seed, vocab, sys_prompt, time.monotonic())
    drv.run(until=lead, send_until=lead + seconds)
    phases["lead_s"] = time.monotonic() - t
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    phases["cache_read_s"] = compiles.read_s
    counters_open = sess.work_stats()
    t_open_abs = time.monotonic()
    setup_s = t_open_abs - t_start
    t_open = drv.now()
    with jax.profiler.TraceAnnotation("window"):
        drv.run(until=t_open + seconds, send_until=t_open + seconds)
    t_close = t_open + seconds
    t_close_abs = time.monotonic()
    counters_close = sess.work_stats()
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.between(t_open_abs, t_close_abs)
    drain_s = float(cell.get("drain_s", 60))
    drv.run(until=t_close + drain_s, send_until=t_close, drain_from=t_open,
            sessions=int(cell["check"]["sequences"]))
    unserved = sum(1 for r in drv.requests if not r.times and t_open <= r.due < t_close)
    log(
        "setup phases: " + ", ".join(f"{k} {v:.3f}" for k, v in phases.items())
        + f"; setup_s {setup_s:.3f}; compile cache {cache_dir}"
    )
    log(
        f"window: {seconds:.1f}s, {drv.next_session} sessions started, "
        f"{len(drv.steps)} steps in all, backend compiles inside the window "
        f"{len(in_window)} {sorted(set(in_window))}, unserved after drain {unserved}, "
        f"largest decode batch {drv.peak_live} of {mix.max_batch}"
    )

    rec = RunRecord(
        conf=conf, seconds=seconds, setup_s=setup_s,
        t_open=t_open, t_close=t_close, requests=drv.requests,
        steps=drv.steps, counters_open=counters_open,
        counters_close=counters_close, peaks=peaks,
    )
    info = device_info(used)
    if trace:
        import trace as trace_mod

        records = trace_mod.events_from_xplane(str(TRACE_DIR))
        rec.trace = trace_mod.reduce(records, kernel_names=cell["kernels"])
        info["busy_s"] = rec.trace["busy_s"]
        info["window_s"] = rec.trace["window_s"]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    due = rec.due_in_window()
    failed = sum(1 for r in due if not r.times)
    metrics = {}
    for m in cell_metrics(bench, cell_name, trace):
        value = load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # The check: the program's state goes first, then the reference runs.
    seqs = sample_sequences(drv.requests, seed, int(cell["check"]["sequences"]))
    try:
        teardown = sess.close()
    except AssertionError as e:  # a leak is reported, the check still runs
        teardown = f"failed: {e}"
    del sess, drv
    gc.collect()
    t = time.monotonic()
    longest = max(
        mix.system_prompt_tokens + sum(k.new_tokens + k.output_tokens for k in p.turns)
        for p in plan
    )
    readings = logit_gaps(family, params, conf, seqs, control=opts.control,
                          pad_to=longest)
    check_s = time.monotonic() - t
    readings["window_compiles"] = len(in_window)
    check, correct = judge(readings, cell["check"]["limits"])
    correct = correct and bool(seqs)
    log(f"teardown sweep: {teardown}; checked {readings['checked_tokens']} served "
        f"tokens in {len(seqs)} sequences in {check_s:.1f}s")
    for name, c in check.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    result = {
        "correct": correct,
        "attempted": len(due),
        "failed": failed,
        "metrics": metrics,
        "device": info,
    }
    if trace:
        result["breakdown"] = {
            "device_ops": rec.trace["device_ops"],
            "idle_gaps": rec.trace["idle_gaps"],
        }
    result["check"] = check
    return result, {"readings": readings, "window_compiles": len(in_window),
                    "phases": phases, "unserved": unserved,
                    "check_s": check_s, "limits": cell["check"]["limits"]}
