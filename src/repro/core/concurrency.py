"""Concurrency estimation (paper §3.2), adapted to TPU HBM budgeting.

The paper probes one client on a GPU, reads VRAM allocation + utilization from
``nvidia-smi``, and derives how many concurrent worker processes the GPU
sustains (Table 3: e.g. 33 on an A40 for TG, 3 on a 2080 Ti for MLM).

On TPU there are no processes: "concurrency" becomes **client slots per
worker group** — how many client-model copies (params + optimizer state +
working set) fit in the group's combined HBM next to the global copy and the
round's activations.  Two estimators are provided:

* :func:`estimate_slots_analytic` — closed-form from parameter/activation
  byte counts (before any compilation exists).
* :func:`estimate_slots_from_memory_analysis` — refined from a compiled
  round step's ``memory_analysis()`` (the TPU analogue of the paper's
  probe-one-client-then-read-nvidia-smi step).

Both return the concurrency level plus the per-slot byte breakdown so the
placement layer can reason about it (the paper's "VRAM-aware" property).
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = [
    "DeviceSpec",
    "ConcurrencyEstimate",
    "estimate_slots_analytic",
    "estimate_slots_from_memory_analysis",
    "gpu_concurrency_probe",
]


@dataclass(frozen=True)
class DeviceSpec:
    """Per-chip hardware description (defaults: TPU v5e-class)."""

    name: str = "tpu-v5e"
    hbm_bytes: int = 16 * 1024 ** 3
    peak_flops: float = 197e12          # bf16
    hbm_bw: float = 819e9               # bytes/s
    ici_bw: float = 50e9                # bytes/s/link
    vmem_bytes: int = 128 * 1024 ** 2
    reserved_fraction: float = 0.08     # runtime/framework reservation


@dataclass(frozen=True)
class ConcurrencyEstimate:
    slots: int
    bytes_per_slot: int
    fixed_bytes: int          # global params + activations, slot-independent
    budget_bytes: int
    detail: str = ""

    def __str__(self):
        return (f"slots={self.slots} slot={self.bytes_per_slot/2**30:.2f}GiB "
                f"fixed={self.fixed_bytes/2**30:.2f}GiB "
                f"budget={self.budget_bytes/2**30:.2f}GiB {self.detail}")


def estimate_slots_analytic(
    *,
    param_bytes: int,
    optimizer_bytes_per_param_byte: float,
    activation_bytes: int,
    group_devices: int,
    device: DeviceSpec = DeviceSpec(),
    max_slots: int = 64,
) -> ConcurrencyEstimate:
    """Closed-form slot estimate for one worker group.

    A slot needs one trainable client copy: params + optimizer state + the
    gradient working set (~1 param copy, reused).  The global model copy and
    the per-step activation working set are shared across slots because slots
    execute sequentially inside a ``lax.scan`` (only their *parameters*
    persist; activations are reused).  Memory is pooled over ``group_devices``
    since all client state is sharded over the worker group's chips.
    """
    budget = int(device.hbm_bytes * (1.0 - device.reserved_fraction)) * group_devices
    fixed = param_bytes + activation_bytes          # global copy + working set
    per_slot = int(param_bytes * (1.0 + optimizer_bytes_per_param_byte + 1.0))
    free = budget - fixed
    slots = max(0, min(max_slots, free // max(per_slot, 1)))
    return ConcurrencyEstimate(
        slots=int(slots), bytes_per_slot=per_slot, fixed_bytes=fixed,
        budget_bytes=budget,
        detail=f"analytic group_devices={group_devices}")


def estimate_slots_from_memory_analysis(
    mem_analysis, *, slots_compiled: int, group_devices: int,
    device: DeviceSpec = DeviceSpec(), max_slots: int = 64,
) -> ConcurrencyEstimate:
    """Refine the analytic estimate from a compiled round step.

    ``mem_analysis`` is ``compiled.memory_analysis()``; we read per-device
    argument/output/temp sizes, attribute the temp+arg growth to the compiled
    slot count, and extrapolate the max slot count that stays in budget.
    Mirrors the paper's probe-then-extrapolate concurrency estimator.
    """
    try:
        arg = int(mem_analysis.argument_size_in_bytes)
        out = int(mem_analysis.output_size_in_bytes)
        tmp = int(mem_analysis.temp_size_in_bytes)
    except AttributeError:  # backend without full analysis: stay conservative
        return ConcurrencyEstimate(slots=slots_compiled, bytes_per_slot=0,
                                   fixed_bytes=0, budget_bytes=0,
                                   detail="memory_analysis unavailable")
    budget = int(device.hbm_bytes * (1.0 - device.reserved_fraction))
    used = arg + out + tmp
    # Slots scale the client-param planes of args/temps ~linearly; treat the
    # whole used set conservatively as slot-linear beyond a fixed floor of the
    # argument size (global params + batches are fixed inputs).
    fixed = arg
    per_slot = max(1, (used - fixed) // max(slots_compiled, 1))
    free = budget - fixed
    slots = max(1, min(max_slots, free // per_slot))
    return ConcurrencyEstimate(
        slots=int(slots), bytes_per_slot=int(per_slot), fixed_bytes=int(fixed),
        budget_bytes=budget,
        detail=f"from memory_analysis; compiled_slots={slots_compiled} "
               f"group_devices={group_devices}")


def gpu_concurrency_probe(vram_bytes: int, client_vram_bytes: int,
                          util_per_client: float, *, max_procs: int = 64) -> int:
    """The paper's original GPU rule, kept for the cluster simulator: probe
    one client, then fit as many processes as VRAM (and compute utilization)
    allow.  Reproduces Table 3 given the simulator's task profiles."""
    by_mem = vram_bytes // max(client_vram_bytes, 1)
    by_util = int(1.0 / max(util_per_client, 1e-6))
    return int(max(1, min(max_procs, by_mem, max(by_util, 1))))
