"""IR pass: Pallas tile legality and VMEM working-set audit.

For every ``pallas_call`` eqn reachable from a target's jaxpr, read the
grid mapping's block mappings (inputs *and* outputs) and check the claims
the kernels' docstrings make by hand today:

* **Divisibility** — each block dim must divide the (padded) operand dim:
  a non-dividing block silently reads out-of-bounds-garbage partial tiles
  on the last grid step.
* **Tiling constraints** — the MXU/VPU consume (sublane, lane) tiles: the
  block's minor dim must be a multiple of 128 and the second-minor a
  multiple of 8 (f32/i32) / 16 (bf16) / 32 (int8) — *unless* the block
  spans the operand's full extent in that dim, which Mosaic handles as a
  single (possibly sub-tile) block (how ``gram`` legally streams (bm, k)
  slabs with k = 4).
* **VMEM budget** — the double-buffered per-step working set (2x the sum
  of block bytes) must fit the ~16 MiB VMEM.  Where a target declares
  ``documented_vmem_bytes`` (``bsr_spmm``'s 68 KiB docstring claim), the
  computed working set must match it — the comment becomes a checked fact.
"""
from __future__ import annotations

from repro.analysis.ir.framework import IRContext, IRPass, IRTarget, \
    register_ir_pass
from repro.analysis.ir.liveness import (
    _pallas_working_set, block_dims, iter_eqns,
)

#: per-core VMEM on current TPUs (v4/v5): ~16 MiB
VMEM_BUDGET = 16 * 1024 * 1024

#: slack on the documented-working-set equality: absorbs scalar-prefetch
#: operands' few bytes without letting a real block-shape change through
_DOC_TOLERANCE = 1024


def _sublane(dtype) -> int:
    itemsize = getattr(dtype, "itemsize", 4)
    return {1: 32, 2: 16}.get(itemsize, 8)


def _kernel_name(eqn) -> str:
    """The kernel function's name, from the kernel jaxpr's debug info."""
    return eqn.params["jaxpr"].debug_info.func_name


@register_ir_pass
class PallasTilesPass(IRPass):
    name = "pallas-tiles"
    description = ("BlockSpecs must divide padded operands, meet dtype "
                   "tiling constraints, and fit the VMEM budget")

    def check(self, target: IRTarget, ctx: IRContext):
        seen = set()
        for eqn, _depth in iter_eqns(target.jaxpr()):
            if eqn.primitive.name != "pallas_call":
                continue
            kname = _kernel_name(eqn)
            if kname in seen:  # same kernel traced at several call sites
                continue
            seen.add(kname)
            yield from self._check_call(kname, eqn, target)

    def _check_call(self, kname, eqn, target: IRTarget):
        gm = eqn.params.get("grid_mapping")
        if gm is None:
            return
        for idx, bm in enumerate(gm.block_mappings):
            sd = bm.array_aval
            block = block_dims(bm)
            shape = tuple(int(d) for d in sd.shape)
            if len(block) != len(shape):
                continue  # mapped-dim mismatch; nothing checkable
            for d, (b, s) in enumerate(zip(block, shape)):
                if b > 0 and s % b:
                    yield (
                        f"kernel `{kname}` operand {idx}: block dim "
                        f"{d} = {b} does not divide the padded operand "
                        f"dim {s} (shape {shape}, block {block}) — the "
                        "last grid step reads a partial tile")
            if len(block) >= 2:
                lane, sub = block[-1], block[-2]
                need_sub = _sublane(sd.dtype)
                if lane % 128 and lane != shape[-1]:
                    yield (
                        f"kernel `{kname}` operand {idx}: minor block dim "
                        f"{lane} is neither a multiple of the 128-lane "
                        f"tile nor the full operand extent {shape[-1]} "
                        f"({sd.dtype})")
                if sub % need_sub and sub != shape[-2]:
                    yield (
                        f"kernel `{kname}` operand {idx}: second-minor "
                        f"block dim {sub} is neither a multiple of the "
                        f"{need_sub}-sublane tile for {sd.dtype} nor the "
                        f"full operand extent {shape[-2]}")

        ws = _pallas_working_set(eqn)
        if 2 * ws > VMEM_BUDGET:
            yield (
                f"kernel `{kname}`: double-buffered VMEM working set "
                f"2 x {ws} = {2 * ws} bytes exceeds the "
                f"{VMEM_BUDGET}-byte VMEM budget — shrink the blocks")
        doc = target.documented_vmem_bytes
        if doc is not None and abs(ws - doc) > _DOC_TOLERANCE:
            yield (
                f"kernel `{kname}`: computed per-step working set {ws} "
                f"bytes does not match the documented {doc} bytes — "
                "update the docstring claim or the BlockSpecs")
