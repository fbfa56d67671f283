"""Static contract checker for the registered Pallas kernels (RCCA1xx).

Every production kernel wrapper launches from a declarative
:class:`~repro.kernels.plan.KernelPlan` built by a pure ``plan_*``
function (see :mod:`repro.kernels.plan`); the registry in
``repro.kernels.KERNEL_REGISTRY`` maps each kernel to its plan builder
plus representative probe shapes.  Because the checker verifies the
*same plan object* the wrapper realizes via ``launch_args``, a passing
check is a statement about what actually runs — there is no duplicated
sizing logic to drift.

Checks, per probe (all pure Python + one ``jax.eval_shape`` trace — no
device, no kernel execution):

  RCCA101  grid/block consistency: block shapes tile the padded operand
           shapes exactly (every padded dim divisible by its block dim),
           ranks agree, grid dims positive.
  RCCA102  index-map validity: every grid position maps each operand to
           an in-range block coordinate (no OOB tile).
  RCCA103  output coverage: walking the full grid visits EVERY tile of
           every output — an uncovered tile is garbage VMEM contents
           silently published to HBM.
  RCCA104  VMEM residency: every block and scratch buffer fits the
           shared per-buffer budget
           (:data:`repro.kernels.matmul.VMEM_BLOCK_ELEMS`); the whole
           charge (:attr:`KernelPlan.vmem_need_bytes`: pipeline
           buffers, body values, slack) fits the scoped limit that
           :func:`repro.kernels.plan.launch_args` requests for the plan
           (Mosaic's 16 MiB default when it requests none); and that
           limit stays under :data:`repro.kernels.plan.VMEM_LIMIT_CAP`.
  RCCA105  dtype rules: scratch accumulators and declared accumulator
           outputs are f32; bf16 inputs never accumulate in bf16.
  RCCA106  abstract-eval agreement: ``jax.eval_shape`` of the live
           wrapper matches the plan's logical output shapes/dtypes.
  RCCA107  autotune-cache validity: every persisted cache entry parses,
           its shape key names padded (×128) dims, and re-planning the
           shape under the entry's block caps yields a plan that passes
           RCCA101–105 — a hand-edited or stale cache cannot smuggle an
           inconsistent launch into production.  Schedule entries
           (``powerpass-staged`` / ``projgram-staged``) must carry a
           ``"staged"|"recompute"`` value and both schedules' plans at
           that shape must still re-plan cleanly.
  RCCA108  PRNG-bearing plans: a ``*_seeded`` kernel draws its Ω tiles
           from a counter-based PRNG, so its ONLY source of randomness
           must be the seed plumbed as an SMEM scalar operand — exactly
           one scalar, integer dtype, a handful of words (a seed, never
           a data array smuggled around the blocked specs).
"""

from __future__ import annotations

import itertools
from typing import List, Optional

from .report import Violation


def _probe_tag(name: str, probe: dict) -> str:
    dims = "x".join(str(v) for k, v in probe.items() if k != "dtype")
    return f"{name}[{dims}|{probe.get('dtype', '?')}]"


def check_plan(plan, *, where: str = "", budget: Optional[int] = None) -> List[Violation]:
    """RCCA101–105 on one :class:`~repro.kernels.plan.KernelPlan`."""
    from repro.kernels.matmul import VMEM_BLOCK_ELEMS
    from repro.kernels.plan import (MOSAIC_DEFAULT_VMEM_LIMIT,
                                    VMEM_LIMIT_CAP, launch_args)

    budget = VMEM_BLOCK_ELEMS if budget is None else budget
    where = where or plan.name
    out: List[Violation] = []

    def v(code: str, msg: str) -> None:
        out.append(Violation(code, where, 0, msg))

    # -- RCCA101: grid & tiling consistency -------------------------------
    if not plan.grid or any(g <= 0 for g in plan.grid):
        v("RCCA101", f"empty/non-positive grid {plan.grid}")
        return out
    specs = [("in", i, b) for i, b in enumerate(plan.in_specs)] + \
            [("out", i, b) for i, b in enumerate(plan.out_specs)]
    tiles = {}
    for kind, i, b in specs:
        tag = f"{kind}_specs[{i}]"
        if len(b.shape) != len(b.padded):
            v("RCCA101", f"{tag}: block rank {len(b.shape)} != padded rank "
              f"{len(b.padded)}")
            continue
        bad = [d for d in range(len(b.shape))
               if b.shape[d] <= 0 or b.padded[d] % b.shape[d] != 0]
        if bad:
            v("RCCA101", f"{tag}: block {b.shape} does not tile padded "
              f"{b.padded} (dims {bad})")
            continue
        tiles[(kind, i)] = tuple(p // s for p, s in zip(b.padded, b.shape))
    if len(plan.out_shape) != len(plan.out_specs):
        v("RCCA101", f"{len(plan.out_shape)} logical out shapes for "
          f"{len(plan.out_specs)} out specs")
    for i, (logical, b) in enumerate(zip(plan.out_shape, plan.out_specs)):
        if len(logical) == len(b.padded) and \
                any(lo > p for lo, p in zip(logical, b.padded)):
            v("RCCA101", f"out_specs[{i}]: logical shape {logical} exceeds "
              f"padded {b.padded}")

    # -- RCCA102 + RCCA103: walk the full grid ----------------------------
    coverage = {i: set() for i in range(len(plan.out_specs))}
    for idx in itertools.product(*(range(g) for g in plan.grid)):
        for kind, i, b in specs:
            if (kind, i) not in tiles:
                continue  # tiling already broken; skip the walk for it
            try:
                coord = tuple(b.index_map(*idx))
            except TypeError:
                v("RCCA102", f"{kind}_specs[{i}]: index map arity does not "
                  f"match grid rank {len(plan.grid)}")
                tiles.pop((kind, i))
                continue
            rng = tiles[(kind, i)]
            if len(coord) != len(rng) or any(
                    not (0 <= c < r) for c, r in zip(coord, rng)):
                v("RCCA102", f"{kind}_specs[{i}]: grid {idx} -> block "
                  f"coord {coord} outside tiling {rng}")
                tiles.pop((kind, i))
                continue
            if kind == "out":
                coverage[i].add(coord)
    for i, b in enumerate(plan.out_specs):
        if ("out", i) not in tiles:
            continue
        want = 1
        for t in tiles[("out", i)]:
            want *= t
        if len(coverage[i]) != want:
            v("RCCA103", f"out_specs[{i}]: grid visits {len(coverage[i])} of "
              f"{want} output tiles — uncovered tiles publish garbage")

    # -- RCCA104: VMEM budget ---------------------------------------------
    for kind, i, b in specs:
        if b.elems > budget:
            v("RCCA104", f"{kind}_specs[{i}]: block {b.shape} = {b.elems} "
              f"elems exceeds VMEM budget {budget}")
    for i, s in enumerate(plan.scratch):
        if s.elems > budget:
            v("RCCA104", f"scratch[{i}]: {s.shape} = {s.elems} elems "
              f"exceeds VMEM budget {budget}")
    limit = launch_args(plan)["compiler_params"].vmem_limit_bytes
    limit = MOSAIC_DEFAULT_VMEM_LIMIT if limit is None else limit
    if plan.vmem_need_bytes > limit:
        v("RCCA104", f"double-buffered blocks + scratch = {plan.vmem_bytes} "
          f"bytes, charged {plan.vmem_need_bytes} with the body's values, "
          f"exceed the scoped VMEM limit {limit} its launch requests")
    if limit > VMEM_LIMIT_CAP:
        v("RCCA104", f"requested scoped VMEM limit {limit} bytes exceeds "
          f"the cap {VMEM_LIMIT_CAP}")

    # -- RCCA105: dtype rules ---------------------------------------------
    for i, s in enumerate(plan.scratch):
        if s.dtype != "float32":
            v("RCCA105", f"scratch[{i}]: accumulator dtype {s.dtype} != "
              "float32")
    for i in plan.accum_outputs:
        if i >= len(plan.out_specs):
            v("RCCA105", f"accum_outputs names out_specs[{i}] which does "
              "not exist")
        elif plan.out_specs[i].dtype != "float32":
            v("RCCA105", f"out_specs[{i}]: declared accumulator output has "
              f"dtype {plan.out_specs[i].dtype} != float32")
    if any(b.dtype == "bfloat16" for b in plan.in_specs) \
            and not plan.accum_outputs \
            and any(b.dtype == "bfloat16" for b in plan.out_specs):
        v("RCCA105", "bf16 inputs with bf16 outputs and no declared f32 "
          "accumulator output — bf16 accumulation loses the contract")

    # -- RCCA108: PRNG-bearing plans — the seed is the only entropy -------
    if plan.name.endswith("_seeded") and len(plan.scalars) != 1:
        v("RCCA108", f"seeded kernel declares {len(plan.scalars)} scalar "
          "operands — the counter-based PRNG contract is exactly one "
          "SMEM seed")
    for i, s in enumerate(plan.scalars):
        if s.dtype not in ("uint32", "int32", "uint64", "int64"):
            v("RCCA108", f"scalars[{i}]: dtype {s.dtype} — scalar operands "
              "are integer seeds/sizes")
        if s.elems > 8:
            v("RCCA108", f"scalars[{i}]: {s.shape} = {s.elems} elems — a "
              "scalar operand is a seed, not a data array routed around "
              "the blocked specs")
    return out


def check_kernel(kdef, *, abstract: bool = True) -> List[Violation]:
    """All probes of one registered kernel, plus the abstract-eval
    cross-check (RCCA106) of the live wrapper against the plan."""
    out: List[Violation] = []
    for probe in kdef.probes:
        where = _probe_tag(kdef.name, probe)
        try:
            plan = kdef.plan(dict(probe))
        except Exception as e:  # noqa: BLE001 — any plan crash is a finding
            out.append(Violation("RCCA101", where, 0,
                                 f"plan builder raised: {e!r}"))
            continue
        if plan is None:
            continue  # documented unfused-fallback shape
        out.extend(check_plan(plan, where=where))
        if not abstract:
            continue
        try:
            import jax

            fn, arg_structs = kdef.abstract(dict(probe))
            res = jax.eval_shape(fn, *arg_structs)
        except Exception as e:  # noqa: BLE001
            out.append(Violation("RCCA106", where, 0,
                                 f"abstract eval raised: {e!r}"))
            continue
        got = [res] if not isinstance(res, (tuple, list)) else list(res)
        if len(got) != len(plan.out_shape):
            out.append(Violation(
                "RCCA106", where, 0,
                f"wrapper returns {len(got)} outputs, plan declares "
                f"{len(plan.out_shape)}"))
            continue
        for i, (g, want) in enumerate(zip(got, plan.out_shape)):
            if tuple(g.shape) != tuple(want):
                out.append(Violation(
                    "RCCA106", where, 0,
                    f"output[{i}]: wrapper abstract shape {tuple(g.shape)} "
                    f"!= plan logical shape {tuple(want)}"))
    return out


# ---------------------------------------------------------------------------
# autotune-cache validation (RCCA107)
# ---------------------------------------------------------------------------


def _plan_from_cache_entry(op: str, dims: List[int], dtype: str, blocks):
    from repro.kernels.matmul import plan_matmul
    from repro.kernels.powerpass import plan_powerpass
    from repro.kernels.projgram import plan_projgram

    b0, b1, b2 = (int(b) for b in blocks)
    if op in ("matmul_nn", "matmul_tn"):
        M, K, N = dims
        return plan_matmul(M, K, N, dtype, transpose_lhs=(op == "matmul_tn"),
                           block_m=b0, block_n=b1, block_k=b2)
    if op == "powerpass":
        n, db, kt, da = dims
        return plan_powerpass(n, da, db, kt, dtype,
                              block_n=b0, block_db=b1, block_da=b2)
    if op == "projgram":
        n, d, kt = dims
        return plan_projgram(n, d, kt, dtype,
                             block_n=b0, block_d=b1, block_c=b2)
    return None


def _plans_from_schedule_entry(op: str, dims: List[int], dtype: str):
    """Every KernelPlan either schedule of a staged-vs-recompute cache
    entry would launch at this shape — the recompute base plus the
    stage/sweep pair — skipping schedules the planners decline."""
    from repro.kernels.powerpass import plan_powerpass, plan_powerpass_staged
    from repro.kernels.projgram import plan_projgram, plan_projgram_staged

    plans = []
    if op == "powerpass-staged":
        n, db, kt, da = dims
        plans.append(plan_powerpass(n, da, db, kt, dtype))
        staged = plan_powerpass_staged(n, da, db, kt, dtype)
        if staged is not None:
            plans.extend(staged)
    elif op == "projgram-staged":
        n, d, kt = dims
        plans.append(plan_projgram(n, d, kt, dtype))
        staged = plan_projgram_staged(n, d, kt, dtype)
        if staged is not None:
            plans.extend(staged)
    return [p for p in plans if p is not None]


def check_autotune_cache(path: Optional[str] = None) -> List[Violation]:
    """RCCA107 over every entry of the persisted autotune cache: shape
    keys must parse to padded dims, blocks must be usable caps, and the
    re-planned launch under those caps must itself pass RCCA101–105.
    A missing cache is clean (autotuning is optional by design)."""
    import json
    import os

    from repro.kernels import autotune

    path = path or autotune.cache_path()
    if not os.path.exists(path):
        return []
    try:
        with open(path) as f:
            cache = json.load(f)
    except (OSError, ValueError) as e:
        return [Violation("RCCA107", path, 0, f"unreadable cache: {e}")]
    if not isinstance(cache, dict):
        return [Violation("RCCA107", path, 0, "cache root is not an object")]

    known_ops = ("matmul_nn", "matmul_tn", "powerpass", "projgram",
                 "powerpass-staged", "projgram-staged")
    ndims = {"matmul_nn": 3, "matmul_tn": 3, "powerpass": 4, "projgram": 3,
             "powerpass-staged": 4, "projgram-staged": 3}
    schedule_ops = ("powerpass-staged", "projgram-staged")
    out: List[Violation] = []
    for key, ent in sorted(cache.items()):
        where = f"{path}[{key}]"
        parts = key.split("|")
        if len(parts) != 4:
            out.append(Violation("RCCA107", where, 0,
                                 "shape key is not backend|op|dtype|dims"))
            continue
        _backend, op, dtype, dim_s = parts
        if op not in known_ops:
            out.append(Violation("RCCA107", where, 0,
                                 f"unknown op {op!r} in shape key"))
            continue
        try:
            dims = [int(d) for d in dim_s.split("x")]
        except ValueError:
            out.append(Violation("RCCA107", where, 0,
                                 f"unparsable dims {dim_s!r}"))
            continue
        if len(dims) != ndims[op]:
            out.append(Violation("RCCA107", where, 0,
                                 f"{op} key carries {len(dims)} dims, "
                                 f"expected {ndims[op]}"))
            continue
        if any(d <= 0 or d % 128 for d in dims):
            out.append(Violation("RCCA107", where, 0,
                                 f"dims {dims} not padded to x128 — keys "
                                 "must name the padded problem"))
            continue
        if op in schedule_ops:
            # schedule entries record a measured staged-vs-recompute
            # winner, not block caps — validate the value and that both
            # schedules still re-plan to launches passing RCCA101–105
            sched = ent.get("schedule") if isinstance(ent, dict) else None
            if sched not in ("staged", "recompute"):
                out.append(Violation("RCCA107", where, 0,
                                     f"schedule entry value {sched!r} not "
                                     "'staged'|'recompute'"))
                continue
            for plan in _plans_from_schedule_entry(op, dims, dtype):
                for v in check_plan(plan, where=where):
                    out.append(Violation("RCCA107", v.path, v.line,
                                         f"schedule entry re-plan invalid: "
                                         f"[{v.code}] {v.message}"))
            continue
        blocks = ent.get("blocks") if isinstance(ent, dict) else None
        try:
            blocks = [int(b) for b in blocks]
            assert len(blocks) == 3 and all(b > 0 for b in blocks)
        except (TypeError, ValueError, AssertionError):
            out.append(Violation("RCCA107", where, 0,
                                 f"entry blocks {blocks!r} not three "
                                 "positive ints"))
            continue
        try:
            plan = _plan_from_cache_entry(op, dims, dtype, blocks)
        except Exception as e:  # noqa: BLE001
            out.append(Violation("RCCA107", where, 0,
                                 f"re-planning under cached blocks raised: "
                                 f"{e!r}"))
            continue
        if plan is not None:
            for v in check_plan(plan, where=where):
                out.append(Violation("RCCA107", v.path, v.line,
                                     f"cached blocks yield invalid plan: "
                                     f"[{v.code}] {v.message}"))
    return out


def check_registry(registry=None, *, abstract: bool = True,
                   cache: bool = True) -> List[Violation]:
    """The full kernel gate: every registered kernel's probes (RCCA101–
    106) plus the persisted autotune cache (RCCA107)."""
    if registry is None:
        from repro.kernels import KERNEL_REGISTRY as registry
    out: List[Violation] = []
    for name in sorted(registry):
        out.extend(check_kernel(registry[name], abstract=abstract))
    if cache:
        out.extend(check_autotune_cache())
    return out
