"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this
module never touches jax device state — the dry-run must set
XLA_FLAGS before anything initialises the backend.

Production target: TPU v5e, 256 chips/pod.
  single pod : (data=16, model=16)
  multi-pod  : (pod=2, data=16, model=16) — "pod" extends the gradient
               all-reduce across the inter-pod (DCN-class) links.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes over the first ``prod(shape)``
    devices: the model code places arrays with ``with_sharding_constraint``
    and ``shard_map``, which need Auto axes (``jax.make_mesh`` defaults to
    Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(n_devices=None):
    """(data=1, model=n) over the host's first ``n_devices`` devices
    (default: all of them)."""
    n = len(jax.devices()) if n_devices is None else n_devices
    return _mesh((1, n), ("data", "model"))


def make_fleet_mesh(n_hosts=None):
    """1-D ``("hosts",)`` mesh for multi-host bucketed fleet training.

    Each mesh entry stands for one simulation host; the fleet drivers
    shard the stacked device axis over it (``sharding.rules.fleet_specs``)
    so resident fleet state — and therefore fleet size — scales linearly
    with hosts.  CI exercises it with fake CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``).
    """
    n = len(jax.devices()) if n_hosts is None else n_hosts
    if n > len(jax.devices()):
        raise ValueError(
            f"fleet mesh wants {n} hosts but only {len(jax.devices())} "
            "devices exist (set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N for fake hosts)")
    return _mesh((n,), ("hosts",))


def make_decode_mesh(n_devices=None):
    """(data, model) mesh shaped for serving decode.

    Decode roofline: at serving batch sizes every step streams the full
    weight + KV working set, so decode is HBM-bandwidth/ICI-bound, not
    FLOPs-bound — splitting weights over "model" multiplies effective
    HBM bandwidth (each chip streams 1/model of the weights per step,
    ~``HBM_BW * model`` aggregate), while the "data" axis only splits
    the (already small) batch.  So the model axis gets as many devices
    as possible: halve the device count into "model" until the data
    residue is odd.  8 devices -> (data=2, model=4); 4 -> (2, 2);
    2 -> (1, 2); 1 -> (1, 1) — the 1-device degenerate mesh is
    bit-identical to running with ``mesh=None``.  The model axis also
    carries the EP all-to-all and head sharding, both ICI-bound at
    ~``ICI_BW``; ``cfg.overlap_a2a`` hides that latency under attention
    compute.
    """
    d = len(jax.devices()) if n_devices is None else n_devices
    return _mesh(decode_mesh_shape(d), ("data", "model"))


def decode_mesh_shape(n_devices: int):
    """(data, model) split for ``make_decode_mesh`` — pure math, so the
    layout is testable without the devices to back it."""
    d, model = n_devices, 1
    while model < d and d % 2 == 0:
        model *= 2
        d //= 2
    return d, model


# v5e hardware constants for the roofline (per chip)
PEAK_FLOPS_BF16 = 197e12     # FLOP/s
HBM_BW = 819e9               # B/s
ICI_BW = 50e9                # B/s per link (~ per chip, one direction)
HBM_BYTES = 16 * 1024 ** 3   # 16 GiB
