"""Serve-graph contract linter: static analysis over jaxprs + Pallas specs.

The paper's whole thesis is a memory contract — the working set must fit in
fast on-chip memory, which is why only 3-bit weights are used. This package
makes the repo's equivalents of that contract machine-checked for every
serving graph, WITHOUT executing any of them:

  no_dequant            no full-shape float weight tensor materialized from
                        a quantized serve form ({"q"}/{"qp"}) outside the
                        Pallas kernels' VMEM tiles
  no_quadratic_scores   no (T, S)-shaped float score tensor in kernel-mode
                        prefill/verify graphs (the flash contract)
  no_host_callback      jitted tick graphs carry no pure_callback /
                        debug_callback / device_put — nothing that syncs or
                        transfers per token
  carry_dtype           every carried buffer (the jitted tick's cache, and
                        every scan/while carry inside it) keeps a fixed
                        dtype across iterations — the PR 5 ``block_decode``
                        bf16 drift class, caught statically
  donation              cache buffers declared donated actually alias an
                        output (no silent copy-fallback warning path)
  vmem_budget           per-kernel VMEM footprint estimated from each
                        ``pallas_call``'s BlockSpecs/grid stays under a
                        byte budget — the on-chip-memory contract itself

Layers:

  jaxpr_utils   shared jaxpr walkers (the one copy of the float-shape /
                primitive scanners the test suite used to triplicate)
  passes        the six checks, each a pure function -> list[Violation]
  vmem          pallas_call -> VMEM footprint estimation
  contracts     the contract-point registry (decode tick, bucketed prefill,
                spec tick, generate loop) + the family x form x mode sweep

Run the sweep: ``python -m repro.analysis --check`` (JSON report; CI gate).
"""
from repro.analysis.passes import (  # noqa: F401
    Violation,
    check_carry_fixed_point,
    check_donation,
    check_no_dequant,
    check_no_host_callback,
    check_no_quadratic_scores,
    check_scan_carries,
    check_vmem_budget,
)
from repro.analysis.contracts import (  # noqa: F401
    DEFAULT_VMEM_BUDGET,
    forbidden_dequant_shapes,
    lint_combo,
    retrace_report,
    run_sweep,
)

__all__ = [
    "Violation", "check_no_dequant", "check_no_quadratic_scores",
    "check_no_host_callback", "check_carry_fixed_point", "check_donation",
    "check_scan_carries", "check_vmem_budget", "forbidden_dequant_shapes",
    "lint_combo", "run_sweep", "retrace_report", "DEFAULT_VMEM_BUDGET",
]
