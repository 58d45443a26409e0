"""The two host wrappers of each stateful kernel, held to one surface.

Every stateful kernel has a single-chip wrapper (``ops/``) and a
sharded one (``parallel/``), and the executors drive either through
the same calls (ROADMAP D7). This file reads the four classes with
``ast`` (no device) and compares their public methods: a wrapper that
gains a public method its twin lacks fails it, and so does a listed
difference that no longer exists, so the lists below can only shrink.
Where both wrappers have a method its parameter names are equal, or
the pair is listed with what differs. A perf PR that lands in one
wrapper has to say here which one it left behind.
"""

import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parent.parent / "risingwave_tpu"

# (single-chip module, class, sharded module, class,
#  single-chip only, sharded only, methods whose parameters differ)
# Every entry is D7's debt: it goes when the wrappers merge.
SURFACES = {
    "join": (
        "ops/hash_join.py", "JoinSideKernel",
        "parallel/join.py", "ShardedJoinKernel",
        {
            # the device payload and degree stores (the sharded kernel
            # keeps both on the host)
            "device_payload_bytes", "read_degrees", "write_degrees",
            # PR 34's and PR 43's books, filed by this wrapper alone
            "take_longest_chain", "take_probe_books",
            "take_probe_rounds",
        },
        {
            # routing, and the mesh's own relations and books
            "drain_overflows", "owners_of", "route_label",
            "shard_tables",
        },
        {
            # the single-chip rebuild reloads the payload store too
            "rebuild": (["key_lanes", "row_refs", "payload"],
                        ["key_lanes", "row_refs"]),
        },
    ),
    "aggregate": (
        "ops/hash_agg.py", "GroupedAggKernel",
        "parallel/agg.py", "ShardedAggKernel",
        {
            # a property here, a plain attribute on the sharded kernel
            "capacity",
            # the cold tier (a mesh plan gets none)
            "evict_keys", "load_groups",
            # PR 34's books
            "take_probe_rounds",
        },
        {
            # routing, elastic resharding, the mesh's relations
            "grow", "owners_of", "reshard", "route_label",
            "shard_tables", "snapshot",
            # the fused prelude is built into the single-chip kernel
            # at construction and set on this one afterwards
            "set_prelude", "supports_prelude",
        },
        {
            # the owners of a raw chunk's rows, for the routing bucket
            "apply_raw": (["raw", "n_visible"],
                          ["raw", "n_visible", "owners"]),
        },
    ),
}


def public_methods(module: str, cls: str) -> dict:
    """{name: parameter names after self} of the class's public
    methods and properties, read from the source."""
    tree = ast.parse((PKG / module).read_text())
    node = next(n for n in tree.body
                if isinstance(n, ast.ClassDef) and n.name == cls)
    out = {}
    for f in node.body:
        if not isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                or f.name.startswith("_"):
            continue
        params = [a.arg for a in
                  f.args.posonlyargs + f.args.args + f.args.kwonlyargs]
        static = any(isinstance(d, ast.Name) and d.id == "staticmethod"
                     for d in f.decorator_list)
        out[f.name] = params if static else params[1:]
    return out


@pytest.mark.parametrize("kernel", sorted(SURFACES))
def test_the_two_wrappers_share_a_surface(kernel):
    (mod_1, cls_1, mod_n, cls_n, only_1, only_n,
     differ) = SURFACES[kernel]
    single = public_methods(mod_1, cls_1)
    sharded = public_methods(mod_n, cls_n)
    for cls, only, listed in (
            (cls_1, set(single) - set(sharded), only_1),
            (cls_n, set(sharded) - set(single), only_n)):
        assert only == listed, (
            f"{cls_1} and {cls_n} drifted: a public method only {cls} "
            "has is either new (give the twin one, or list it here "
            "with its debt) or listed and gone (strike it)")
    got = {m: (single[m], sharded[m])
           for m in set(single) & set(sharded)
           if single[m] != sharded[m]}
    assert got == differ, (
        f"a method of both {cls_1} and {cls_n} takes other parameters "
        "in one than in the other: name them alike, or list the pair")
