"""Peak rates of the chips, and the work of the kernels, from their shapes.

A kernel's roofline share is the least time the chip could take for the
kernel's operations and bytes, max(ops / peak FLOP/s, bytes / peak
bytes/s), over the kernel's measured device time.  Whichever term is the
larger is the bound that applies.
"""
from __future__ import annotations

# Keyed by `device.device_kind` as JAX reports it.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16, the chip's headline rate
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}


def peak(device_kind: str) -> dict:
    """The peaks of a device kind; a kind that is not in the table is an
    error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; add "
                       f"it to perfbench/peaks.py with its source") from None


# f32 operations per simulated step of one scenario in
# kernels/fused_step.py, counted from the formulas the kernel evaluates
# (a compare, select, min or max counts as one operation, as an add or a
# multiply does).  The masked lane sums by which the kernel reads one step
# of a block are an artifact of the tiling and are not counted.
FUSED_STEP_OPS = {
    "dequant": 8,       # 4 trace rows x (scale multiply + zero add)
    "cooling": 16,      # economizer fraction 4, chiller COP 6, fan 1,
                        # chiller power 2, tower water 3
    "heat_reuse": 10,   # fraction 4, chiller heat 2, reclaim 2, scale 2
    "load": 1,          # IT + cooling
    "renewables": 6,    # PV output 2, net load and surplus 4
    "battery": 13,      # carbon policy 6, surplus-aware dispatch 5, casts 2
    "block_sums": 10,   # 5 series x (valid mask multiply + add)
    "recurrence": 27,   # charge 6, discharge 8, state of charge 6,
                        # was-charging 1, PV to battery and grid 6
    "pricing": 6,       # window close 1, demand charge 3, window peak 2
    "accumulators": 16, # grid, grid x CI, grid x price, grid peak, charge,
                        # discharge, export, export x price, curtailed
}
# f32 words read per simulated step of one scenario: the dense block (8
# rows: IT draw, battery threshold, CI rising, two price bands, window
# close flag, two unused) and the four trace rows (carbon intensity,
# wet-bulb, price, PV capacity factor).
_DENSE_ROWS = 8
_TRACE_ROWS = 4
_OUT_WORDS = 128        # the accumulator row written once per scenario
_SMEM_WORDS = 16        # trace scales/zeros (8) and parameters (8)


def fused_step_work(n_steps: int, n_scenarios: int,
                    trace_bytes: int = 4) -> tuple[float, float]:
    """(operations, bytes) that `fused_facility_totals` needs for
    `n_scenarios` runs of `n_steps` steps with every technique on.

    Steps are counted unpadded: the kernel's tail tile pads the horizon to
    a multiple of its block, and that padding is not work."""
    ops = sum(FUSED_STEP_OPS.values()) * n_steps * n_scenarios
    per_scenario = (n_steps * (_DENSE_ROWS * 4 + _TRACE_ROWS * trace_bytes)
                    + (_OUT_WORDS + _SMEM_WORDS) * 4)
    return float(ops), float(per_scenario * n_scenarios)


def roofline_pct(ops: float, nbytes: float, seconds: float,
                 device_kind: str) -> tuple[float, str]:
    """(share of the roofline in %, the bound that applies)."""
    p = peak(device_kind)
    t_ops = ops / p["flops_per_s"]
    t_mem = nbytes / p["hbm_bytes_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
