"""Parse collective traffic out of compiled (SPMD-partitioned) HLO text.

`cost_analysis()` has no collective-byte counter, so we sum the per-device
result payload of every collective op in the partitioned module. Shapes in
post-SPMD HLO are already per-device, so result bytes ≈ bytes crossing the
ICI per device per op (ring all-reduce moves ~2·(n−1)/n ≈ 2× that; we report
raw payload and apply the ring factor in the roofline term).

Ops counted: all-gather, all-reduce, reduce-scatter, all-to-all,
collective-permute (+ their -start/-done async forms, deduped by id).
"""

from __future__ import annotations

import math
import re
from collections import Counter, defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# One collective instruction per line, e.g.
#   %all-reduce.42 = f32[16,1024]{1,0} all-reduce(...)
#   %all-gather-start = (bf16[8,128]{1,0:T(8,128)(2,1)}, bf16[32,128]{...})
#       all-gather-start(...)
# The result shape is everything between "=" and the op name: a plain shape,
# or a tuple whose TPU layouts carry their own parentheses, so it is taken
# as a whole and its array shapes are read out with _ARRAY_RE.
_OP_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*(.+?)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")"
    r"(-start)?\("
)

_ARRAY_RE = re.compile(r"(\w+)\[([\d,]*)\]")

# Async starts whose result tuple is (operands, results[, contexts]); an
# all-reduce-start returns its results directly.
_OPERAND_TUPLE_STARTS = ("all-gather", "collective-permute")


def _result_shapes(
    shape_text: str, kind: str, is_start: bool
) -> list[tuple[str, tuple[int, ...]]]:
    """(dtype, dims) of a collective's result arrays."""
    if (is_start and kind in _OPERAND_TUPLE_STARTS
            and shape_text.startswith("(")):
        parts = _top_level_parts(shape_text[1:-1])
        if len(parts) >= 2:
            shape_text = parts[1]
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _ARRAY_RE.findall(shape_text)]


def _top_level_parts(text: str) -> list[str]:
    """Split a tuple body on the commas that are not inside (), [] or {}."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(text[start:i])
            start = i + 1
    parts.append(text[start:])
    return parts


def parse_collective_bytes(hlo_text: str) -> dict:
    """Returns {"total_bytes": int, "by_kind": {kind: bytes}, "count": int}."""
    by_kind: dict[str, int] = defaultdict(int)
    count = 0
    for _, kind, shapes in iter_collectives(hlo_text):
        by_kind[kind] += sum(_DTYPE_BYTES.get(dt, 0) * math.prod(dims)
                             for dt, dims in shapes)
        count += 1
    return {
        "total_bytes": int(sum(by_kind.values())),
        "by_kind": dict(by_kind),
        "count": count,
    }


# The sharded-serving hot-path invariant (repro.dist): reuse-cache state may
# never be GATHERED across the mesh — the once-per-window counter all-reduce
# is the only allowed cross-shard movement. These are the collective kinds
# that move shard-resident state to other shards wholesale.
_GATHER_KINDS = ("all-gather", "all-to-all")


def iter_collectives(hlo_text: str):
    """Yield (name, kind, [(dtype, dims_tuple), ...]) per collective result.

    Shapes are the RESULT shapes (post-SPMD HLO: per-device locals; an
    all-gather's result is the gathered — global — extent along its axis).
    Async -start/-done pairs dedupe to the -start op.
    """
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if m is None:
            continue
        name, shape_text, kind, start = m.groups()
        if name.endswith(".clone") or "-done" in name:
            continue
        yield name, kind, _result_shapes(shape_text.strip(), kind, bool(start))


# A Pallas kernel lowers to a `tpu_custom_call` whose op_name metadata ends
# in "<kernel name>/pallas_call" (the `name=` given to pl.pallas_call).
_PALLAS_NAME_RE = re.compile(r'op_name="[^"]*?([\w.\-]+)/pallas_call"')


def pallas_kernel_calls(hlo_text: str) -> dict[str, int]:
    """Count the compiled Pallas kernels in TPU HLO text, by kernel name.
    Empty on a backend that ran no Pallas kernel."""
    calls: Counter = Counter()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _PALLAS_NAME_RE.search(line)
        calls[m.group(1) if m else "<unnamed>"] += 1
    return dict(calls)


def cache_collective_violations(
    hlo_text: str, cache_signatures: set
) -> list[dict]:
    """All-gather/all-to-all ops in compiled HLO whose result shape matches a
    reuse-cache buffer signature — the no-gather hot-path assertion.

    `cache_signatures` is `repro.dist.shard.cache_shape_signatures(cache)`:
    (hlo_dtype, dims) of every cache leaf at both its GLOBAL and per-device
    LOCAL shape. An all-gather materializing a cache leaf's global shape (or
    an all-to-all reshuffling its local shape) is exactly the cross-shard
    cache movement the sharded design forbids; activation collectives (whose
    shapes don't carry the cache's [layer, shard] leading dims) pass through.
    Returns one {op, kind, dtype, dims} per offending op — empty = invariant
    holds.
    """
    violations = []
    for name, kind, shapes in iter_collectives(hlo_text):
        if kind not in _GATHER_KINDS:
            continue
        for dt, dims in shapes:
            if (dt, dims) in cache_signatures:
                violations.append(
                    {"op": name, "kind": kind, "dtype": dt, "dims": dims}
                )
    return violations


def summarize_cost(cost: dict | None) -> dict:
    if not cost:
        return {}
    keep = {}
    for k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds"):
        if k in cost:
            keep[k] = float(cost[k])
    # per-memory-space byte counters (bytes accessed0{} etc.)
    for k, v in cost.items():
        if isinstance(v, (int, float)) and k.startswith("bytes accessed"):
            keep[k] = float(v)
    return keep
