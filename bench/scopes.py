"""Per-round layer times from the traced window, through the program's scopes.

The program names its layers with ``jax.named_scope`` (``SCOPES``). The
device operations of a TPU trace carry no scope: an ``XLA Ops`` event is
named by its HLO instruction alone. The compiled program's HLO text does:
each instruction's ``metadata={op_name="jit(train_steps)/.../deleda.mix/
..."}`` holds the scope path it was traced under. A scope's device time is
therefore a join: trace event -> instruction name -> ``op_name`` -> scopes
(``scope_seconds``, pure; ``bench/tests/test_scopes.py``).

An instruction that carries no metadata of its own, one that XLA adds,
takes the path of the scoped loop that runs it, else that of the nearest
instruction whose result it reads (``op_names``).

``round_split`` gives the training cells' per-layer readers their numbers.
It takes the executed program's text by lowering and compiling
``deleda.train_steps`` again at the window's shapes, after the window,
joins it with the window's trace once per run, keeps the result on the
``Layer`` and prints the table to standard error. Where the
program has no scopes, as before they were added, every reader finds
nothing and its metric is left out.
"""

from __future__ import annotations

import re
import sys
import traceback
from collections import defaultdict, deque
from typing import NamedTuple

from bench import trace

# the program's scopes, each with the per-layer metric that reads it
SCOPES = ("deleda.estep", "estep.gather", "estep.sweeps", "estep.scatter",
          "deleda.mix", "deleda.blend", "deleda.record")
# the training round's top-level scopes: the layers a round is split into
ROUND = ("deleda.mix", "deleda.estep", "deleda.blend", "deleda.record")

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(?:body|condition|calls|true_computation"
                     r"|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
# the opcode and its operand list: " fusion(%a, %b)", " copy(%x)"
_OPERANDS = re.compile(r" ([a-z][a-z0-9\-]*)\(((?:%[\w.\-]+(?:, )?)*)\)")
_COMMENT = re.compile(r"/\*.*?\*/")      # the printer's /*index=5*/
_INDEX = re.compile(r"\bindex=(\d+)")
_TOKEN = re.compile(r"[\w.\-]+")


def _names_a_scope(path: str) -> bool:
    return any(s in SCOPES for s in _TOKEN.findall(path))


def op_names(hlo_text: str) -> dict[str, str]:
    """Each instruction of an HLO module's text -> an ``op_name`` path.

    An instruction's own path where it has one. XLA adds instructions
    that carry none: a loop body's copies and bound checks, and whole
    loops and kernels of its own, such as the sort, scatter and relayout
    loops it expands a batched scatter into. Such an instruction takes
    the path of the loop or call that runs it where that names a scope,
    else the nearest scoped path among what it reads (breadth first
    through operands without a path of their own; a loop body's parameter
    stands for the loop's operand), else the enclosing call's: data that
    XLA moves is charged to the scope that made it.
    """
    own, computation_of, caller = {}, {}, {}
    opcode, operands, element = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name, rest = m.groups()
        computation_of[name] = computation
        meta = _OP_NAME.search(rest)
        if meta is not None:
            own[name] = meta.group(1)
        args = _OPERANDS.search(_COMMENT.sub("", rest))
        operands[name] = []
        if args is not None:
            opcode[name] = args.group(1)
            operands[name] = re.findall(r"%([\w.\-]+)", args.group(2))
            if args.group(1) == "get-tuple-element":
                element[name] = int(_INDEX.search(rest).group(1))
        callees = _CALLED.findall(rest)
        for group in _BRANCHES.findall(rest):
            callees += _TOKEN.findall(group)
        for callee in callees:
            caller.setdefault(callee, name)

    def sources(name):
        """What ``name`` reads; an element of a loop body's parameter is
        read from the tuple the loop was started with."""
        ops = operands.get(name, [])
        if name in element:
            loop = caller.get(computation_of.get(ops[0]))
            if opcode.get(ops[0]) is None and opcode.get(loop) == "while" \
                    and operands[loop]:           # a loop body's parameter
                init = operands[loop][0]
                elements = operands.get(init, [])
                if opcode.get(init) == "tuple" and \
                        element[name] < len(elements):
                    return [elements[element[name]]]
                return [init]
        return ops

    def producer(name):
        """The nearest scoped path among what ``name`` reads; the search
        stops at each instruction that has a path of its own."""
        queue, seen = deque(sources(name)), {name}
        while queue:
            n = queue.popleft()
            if n in seen:
                continue
            seen.add(n)
            if n not in own:
                queue.extend(sources(n))
            elif _names_a_scope(own[n]):
                return own[n]
        return ""

    paths = {}

    def resolve(name):
        if name in paths:
            return paths[name]
        if name in own:
            path = own[name]
        else:
            call = caller.get(computation_of.get(name))
            enclosing = resolve(call) if call is not None else ""
            path = (enclosing if _names_a_scope(enclosing)
                    else producer(name) or enclosing)
        paths[name] = path
        return path

    for name in computation_of:
        resolve(name)
    return paths


class ScopeTimes(NamedTuple):
    """Device seconds in a program's executions, averaged over devices."""

    per_scope: dict      # scope -> inclusive seconds, every SCOPES entry
    scoped_s: float      # ops under any ROUND scope
    unresolved_s: float  # ops whose instruction the HLO text does not hold
    unscoped: list       # [[op, seconds]] under no ROUND scope, largest first
    total_s: float       # every op (containers left out)


def scope_seconds(summary: trace.Summary, hlo_text: str,
                  within: str = "train_steps") -> ScopeTimes:
    """Join the device ops run inside ``within``'s executions with the
    scopes of the program's HLO text.

    Scope time is inclusive: an op counts toward every scope on its path,
    so ``deleda.estep`` holds the three ``estep.*`` scopes. Container ops
    (``trace.CONTAINERS``) are left out, as ``Summary.top_ops`` leaves them.
    """
    paths = op_names(hlo_text)
    scopes_of = {}                  # instruction -> the scopes on its path
    per_scope = dict.fromkeys(SCOPES, 0.0)
    unscoped = defaultdict(float)
    scoped = unresolved = total = 0.0
    share = 1.0 / len(summary.devices)
    for d in summary.devices:
        runs = [(m.start_ns, m.end_ns) for m in d.modules
                if m.name.startswith(within)
                or m.name.startswith("jit_" + within)]
        for e in d.ops:
            if not any(a <= e.start_ns < b for a, b in runs):
                continue
            name = trace.short_name(e.name)
            head = name.split(" ", 1)[0]
            if head.split(".", 1)[0] in trace.CONTAINERS:
                continue
            secs = e.dur_ns * 1e-9 * share
            total += secs
            if head not in paths:
                unresolved += secs
                continue
            found = scopes_of.get(head)
            if found is None:
                tokens = set(_TOKEN.findall(paths[head]))
                found = scopes_of[head] = [s for s in SCOPES if s in tokens]
            for s in found:
                per_scope[s] += secs
            if any(s in ROUND for s in found):
                scoped += secs
            else:
                unscoped[name] += secs
    ranked = sorted(unscoped.items(), key=lambda kv: -kv[1])
    return ScopeTimes(per_scope, scoped, unresolved,
                      [[n, s] for n, s in ranked], total)


def train_steps_text(config: dict, segment_rounds: int) -> str:
    """The compiled text of ``deleda.train_steps`` as
    ``bench/drivers/train_rounds.py`` calls it in the window: its
    configuration, its segment and the shapes and dtypes of its arguments
    (uncommitted, on the default device)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from bench.drivers import train_rounds
    from repro.core import deleda

    n, d = config["n_nodes"], config["docs_per_node"]
    l = config["doc_len_max"]
    dcfg = deleda.DeledaConfig(
        lda=train_rounds._lda(config), mode=config["mode"],
        batch_size=config["batch_size"], rho_kappa=config["rho_kappa"],
        rho_t0=config["rho_t0"])
    state = jax.eval_shape(lambda: deleda.init_state(
        dcfg, jax.random.key(0), n))
    sds = jax.ShapeDtypeStruct
    seg = segment_rounds
    lowered = deleda.train_steps.lower(
        dcfg, state, sds((n, d, l), jnp.int32), sds((n, d, l), jnp.bool_),
        sds((seg, n), jnp.int32), sds((seg, n), jnp.float32),
        sds((seg, n), jnp.bool_), record_every=seg)
    # Compiled afresh. JAX hands back the executable the window ran, and
    # that may have come from the persistent cache, whose key leaves debug
    # information out, scopes included: the executable of another program
    # with the same instructions and none of these scopes. A compiler
    # option (at XLA's default, so the program is the same) makes JAX
    # compile again, and the cache is off for it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile(
            {"xla_dump_disable_metadata": False}).as_text() or ""
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def round_split(layer) -> ScopeTimes | None:
    """The traced window's ``ScopeTimes``, computed once per ``Layer`` and
    kept on it; None where the program has no round scopes or the text
    cannot be had."""
    if not hasattr(layer, "scope_times"):
        layer.scope_times = _split(layer)
    return layer.scope_times


def round_ms(layer, scope: str) -> float | None:
    """Inclusive device ms of ``scope`` a round in the traced window."""
    times = round_split(layer)
    if times is None or times.per_scope[scope] <= 0:
        return None
    return 1e3 * times.per_scope[scope] / layer.counters["rounds"]


def _split(layer) -> ScopeTimes | None:
    if layer.window is None or not layer.counters.get("rounds"):
        return None
    try:
        text = train_steps_text(layer.config, layer.counters["record_every"])
    except Exception:   # a reader must not end the run: report, read nothing
        traceback.print_exc(file=sys.stderr)
        return None
    times = scope_seconds(layer.window, text)
    if times.scoped_s <= 0:
        print("scopes: no device time under the round's scopes",
              file=sys.stderr)
        return None
    _report(times, layer.counters["rounds"])
    return times


def _report(times: ScopeTimes, rounds: int):
    ms = 1e3 / rounds
    unscoped_s = sum(s for _n, s in times.unscoped)
    rows = [f"  {s:<14} {v * ms:10.3f}" for s, v in times.per_scope.items()]
    print(f"scopes: device ms a round over {rounds} rounds of train_steps\n"
          + "\n".join(rows)
          + f"\n  {'unscoped':<14} {unscoped_s * ms:10.3f}"
          f"\n  {'unresolved':<14} {times.unresolved_s * ms:10.3f}"
          f"\n  {'all ops':<14} {times.total_s * ms:10.3f}",
          file=sys.stderr)
    print("scopes: top unscoped ops (ms a round):",
          [[n, s * ms] for n, s in times.unscoped[:5]], file=sys.stderr,
          flush=True)
