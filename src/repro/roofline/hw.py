"""Hardware constants for the roofline/energy models.

Target platform: Google TPU v5e. These constants parameterize the analytical
models only and are never used to configure XLA; :func:`device_chip` refuses
a TPU they do not describe.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip capability + power envelope."""

    name: str
    # Compute.
    peak_flops_bf16: float  # FLOP/s
    peak_flops_f32: float  # FLOP/s
    # Memory.
    hbm_bytes: float
    hbm_bw: float  # bytes/s
    vmem_bytes: float
    # Interconnect (per-link, per-direction).
    ici_bw: float  # bytes/s per link
    ici_links: int  # links per chip
    # Power model (see energy/model.py for calibration notes).
    p_idle_w: float
    p_peak_w: float
    # DVFS axis (autotune/): relative core-frequency grid the autotuner may
    # select from; 1.0 is the calibration point of the constants above.
    freq_points: tuple[float, ...] = (0.6, 0.8, 1.0)
    # Voltage floor as a fraction of nominal V as f -> 0; V scales linearly
    # with f above the floor (the classic P_dyn ~ f * V^2 DVFS model).
    v_floor: float = 0.5

    def v_frac(self, freq: float) -> float:
        """Relative supply voltage at relative core frequency ``freq``."""
        return self.v_floor + (1.0 - self.v_floor) * freq

    def at_freq(self, freq: float) -> "ChipSpec":
        """This chip downclocked to relative core frequency ``freq``.

        The compute engines and their dynamic power envelope scale with the
        core clock (``P_dyn ~ f * V(f)^2``, ``V`` linear in ``f`` down to
        ``v_floor``); the HBM and ICI run their own clock domains and are
        held flat. That asymmetry is what makes slow-and-efficient beat
        race-to-idle on memory-bound sparse kernels (time barely moves,
        dynamic energy drops) and lose on compute-bound ones (time — and
        with it static energy — grows 1/f). Static (idle) power is leakage
        and does not scale with the core clock.
        """
        if not 0.0 < freq <= 1.0:
            raise ValueError(f"relative frequency must be in (0, 1]: {freq}")
        if freq == 1.0:
            return self
        v = self.v_frac(freq)
        dyn = (self.p_peak_w - self.p_idle_w) * freq * v * v
        return dataclasses.replace(
            self,
            name=f"{self.name}@f{freq:g}",
            peak_flops_bf16=self.peak_flops_bf16 * freq,
            peak_flops_f32=self.peak_flops_f32 * freq,
            p_peak_w=self.p_idle_w + dyn,
        )


TPU_V5E = ChipSpec(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=98.5e12,
    hbm_bytes=16 * 2**30,
    hbm_bw=819e9,
    vmem_bytes=128 * 2**20,
    ici_bw=50e9,
    ici_links=4,
    p_idle_w=60.0,
    p_peak_w=215.0,
)


@dataclasses.dataclass(frozen=True)
class HostSpec:
    """Host (CPU socket) power envelope — LIKWID/RAPL-style socket scope."""

    name: str
    p_idle_w: float
    p_active_w: float  # additional power when the host is driving collectives/IO


HOST_XEON = HostSpec(name="xeon_gold_2s", p_idle_w=90.0, p_active_w=35.0)

# Default platform used across roofline + energy accounting.
DEFAULT_CHIP = TPU_V5E
DEFAULT_HOST = HOST_XEON

# JAX ``device_kind`` -> the chip whose peaks price it. A TPU missing here
# has no peaks in this package: pricing it as a v5e would be wrong.
CHIPS = {"TPU v5 lite": TPU_V5E, "TPU v5e": TPU_V5E}


def device_chip() -> ChipSpec:
    """The :class:`ChipSpec` of the TPU that JAX runs on.

    Off the TPU (CPU tests, emulated shards) the models describe the
    target chip, :data:`DEFAULT_CHIP`. On a TPU whose ``device_kind`` is not
    in :data:`CHIPS` this raises instead of assuming v5e peaks."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return DEFAULT_CHIP
    chip = CHIPS.get(dev.device_kind)
    if chip is None:
        raise ValueError(
            f"no chip spec for TPU device_kind {dev.device_kind!r}; the "
            f"energy and roofline models know {sorted(CHIPS)}"
        )
    return chip
