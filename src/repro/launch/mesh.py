"""Production meshes.

Functions, not module constants, so importing never touches jax device
state.  Target hardware: TPU v5e pods — 256 chips/pod in a 16x16 mesh;
the multi-pod config is 2 pods = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# TPU v5e hardware constants (per chip) — used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (approx, per direction)


def _auto_mesh(shape, axes, devices=None):
    """The one place a mesh is built.  Every axis is ``Auto``: the
    installed JAX makes ``Explicit`` axes by default, under which the
    embedding gather of a vocab-sharded table raises
    ``ShardingTypeError``; the executors rely on GSPMD propagation."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def check_mp_divisibility(model_cfg, mp: int, *, spec: str = "") -> None:
    """Fail fast when ``mp`` can't partition a model's param schema.

    Runs the REAL sharding resolver (``sharding.model_axis_fallbacks``
    — divisibility fallbacks included) over the config's schema on a
    stub ``mp``-wide mesh, so the validation can never diverge from
    what the executor will actually do.  Leaves that would silently
    replicate over the ``model`` axis raise ``ValueError`` naming the
    config and every offending tensor, instead of an opaque XLA
    sharding failure (or silently burned devices) at first decode.
    No jax devices are touched — safe to call before mesh creation.
    """
    if mp <= 1:
        return
    from types import SimpleNamespace

    from repro.models.transformer import decoder_param_schema
    from repro.sharding import model_axis_fallbacks

    stub = SimpleNamespace(axis_names=("data", "model"),
                           shape={"data": 1, "model": mp})
    _, fallbacks = model_axis_fallbacks(decoder_param_schema(model_cfg),
                                        stub)
    if fallbacks:
        raise ValueError(
            f"serving mesh {spec or f'mp={mp}'} cannot tensor-parallel "
            f"model {model_cfg.name!r}: mp={mp} divides no dim of "
            f"{', '.join(fallbacks)} — these tensors would silently "
            "replicate over the model axis; pick an mp that divides "
            "the model's head/FFN/vocab dims")


def make_serving_mesh(spec: str, model_cfg=None, *, devices=None):
    """Parse a ``dp=N[,mp=M]`` flag into a ``("data", "model")`` mesh.

    The serving executors shard the continuous engine's slot dimension
    over the ``data`` axis and — with ``mp > 1`` — the model's
    attention-head / FFN / vocab dims over the ``model`` axis (tensor
    parallel).  Pass the target ``model_cfg`` to validate up front that
    ``mp`` divides those dims (:func:`check_mp_divisibility`) instead
    of silently replicating params.  ``dp * mp`` must equal the
    number of ``devices`` (default: every visible device) — use
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to test
    multi-device layouts on a CPU host.
    """
    parts = dict(kv.split("=", 1) for kv in spec.split(",") if kv)
    unknown = set(parts) - {"dp", "mp"}
    if unknown:
        raise ValueError(f"unknown mesh axes {sorted(unknown)} in {spec!r} "
                         "(expected dp=N[,mp=M])")
    dp = int(parts.get("dp", 1))
    mp = int(parts.get("mp", 1))
    if model_cfg is not None:
        check_mp_divisibility(model_cfg, mp, spec=spec)
    devices = list(jax.devices() if devices is None else devices)
    if dp * mp != len(devices):
        raise ValueError(
            f"mesh {spec!r} needs {dp * mp} devices but {len(devices)} "
            "were given (set XLA_FLAGS="
            "--xla_force_host_platform_device_count)")
    return _auto_mesh((dp, mp), ("data", "model"), devices)
