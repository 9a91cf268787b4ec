"""Per-round layer times of the node-sharded training round, from its window.

``bench/scopes.py`` joins a traced window with the compiled text of the
one-device ``train_steps``. This does the same for a cell whose nodes are
sharded over a mesh (``bench/drivers/mesh_rounds.py``): it compiles
``deleda.train_steps`` with that driver's mesh configuration at the
window's shapes and shardings, the persistent cache off as
``scopes.train_steps_text`` has it (a cached executable has lost its
scopes), and joins the text with the window through
``scopes.scope_seconds``, whose device times are averaged over the chips.
The collective-permutes under the ``mix.permute`` scope, one ppermute
pass of the mix each, are timed apart (``permute_seconds``). The result
is computed once per ``Layer`` and kept on it; where the program has no
such scopes, every reader finds nothing and its metric is left out.
"""

from __future__ import annotations

import re
import sys
import traceback
from typing import NamedTuple

from bench import scopes, trace

PERMUTE = "mix.permute"
# an instruction whose opcode is a collective-permute (whole, or async)
_PERMUTE_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^=]*? "
                         r"collective-permute(?:-start|-done)?\(", re.M)


class MeshSplit(NamedTuple):
    times: scopes.ScopeTimes     # the round's scopes, averaged over chips
    permute_s: float             # collective-permutes under PERMUTE


def permute_seconds(summary: trace.Summary, hlo_text: str,
                    within: str = "train_steps") -> float:
    """Device seconds of the collective-permute instructions (start, done
    or whole) whose ``op_name`` path holds ``PERMUTE``, in ``within``'s
    executions, averaged over the devices."""
    paths = scopes.op_names(hlo_text)
    permutes = {name for name in _PERMUTE_OP.findall(hlo_text)
                if PERMUTE in paths.get(name, "").split("/")}
    secs = 0.0
    for d in summary.devices:
        runs = [(m.start_ns, m.end_ns) for m in d.modules
                if m.name.startswith(within)
                or m.name.startswith("jit_" + within)]
        for e in d.ops:
            head = trace.short_name(e.name).split(" ", 1)[0]
            if head in permutes and any(a <= e.start_ns < b
                                        for a, b in runs):
                secs += e.dur_ns * 1e-9
    return secs / len(summary.devices)


def train_steps_text(config: dict, segment_rounds: int, chips: int) -> str:
    """The compiled text of the node-sharded ``deleda.train_steps`` as the
    mesh driver calls it in the window, on the first ``chips`` devices."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench.drivers import mesh_rounds
    from repro.core import deleda

    dcfg = mesh_rounds.mesh_config(config, jax.devices()[:chips])
    node = NamedSharding(dcfg.mesh, P(mesh_rounds.AXIS))
    rep = NamedSharding(dcfg.mesh, P())
    n, d = config["n_nodes"], config["docs_per_node"]
    l, seg = config["doc_len_max"], segment_rounds

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    state = deleda.TrainState(
        stats=sds((n, config["n_topics"], config["vocab_size"]),
                  jnp.float32, node),
        steps=sds((n,), jnp.int32, node),
        key=sds((), jax.random.key(0).dtype, rep),
        t=sds((), jnp.int32, rep), stats_version=sds((), jnp.int32, rep),
        member=sds((n,), jnp.bool_, node), cursor=sds((), jnp.int32, rep))
    lowered = deleda.train_steps.lower(
        dcfg, state, sds((n, d, l), jnp.int32, node),
        sds((n, d, l), jnp.bool_, node), sds((seg, n), jnp.int32, rep),
        sds((seg, n), jnp.float32, rep), sds((seg, n), jnp.bool_, rep),
        record_every=seg)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile(
            {"xla_dump_disable_metadata": False}).as_text() or ""
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def round_split(layer) -> MeshSplit | None:
    """The traced window's ``MeshSplit``, computed once per ``Layer``."""
    if not hasattr(layer, "mesh_split"):
        layer.mesh_split = _split(layer)
    return layer.mesh_split


def round_ms(layer, scope: str) -> float | None:
    """Inclusive device ms of ``scope`` a round, averaged over the chips."""
    split = round_split(layer)
    if split is None or split.times.per_scope[scope] <= 0:
        return None
    return 1e3 * split.times.per_scope[scope] / layer.counters["rounds"]


def permute_ms(layer) -> float | None:
    """Device ms a round of the mix's ppermute passes, averaged over chips."""
    split = round_split(layer)
    if split is None or split.permute_s <= 0:
        return None
    return 1e3 * split.permute_s / layer.counters["rounds"]


def _split(layer) -> MeshSplit | None:
    if layer.window is None or not layer.counters.get("rounds"):
        return None
    try:
        text = train_steps_text(layer.config, layer.counters["record_every"],
                                layer.chips)
    except Exception:   # a reader must not end the run: report, read nothing
        traceback.print_exc(file=sys.stderr)
        return None
    times = scopes.scope_seconds(layer.window, text)
    if times.scoped_s <= 0:
        print("mesh scopes: no device time under the round's scopes",
              file=sys.stderr)
        return None
    split = MeshSplit(times, permute_seconds(layer.window, text))
    scopes._report(times, layer.counters["rounds"])
    print(f"mesh scopes: {PERMUTE} collective-permutes "
          f"{1e3 * split.permute_s / layer.counters['rounds']:.3f} ms a round",
          file=sys.stderr, flush=True)
    return split
