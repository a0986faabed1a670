"""Core JAX types: padded arrays and model input/output containers.

TPU-first equivalents of the reference's
``/root/reference/vizier/_src/jax/types.py:40,165,176,189``. ``PaddedArray``
is the recompile-avoidance mechanism: trial counts and feature dims are
padded to quantized shapes (see ``converters.padding``) with per-axis boolean
validity masks, so XLA sees a small set of static shapes while the *actual*
counts stay traced values. Every downstream kernel must thread the masks —
fill values leak into Cholesky factors and acquisitions otherwise.

All containers are registered pytrees (``flax.struct``) so they pass through
``jit``/``vmap``/``shard_map`` and can carry ``NamedSharding`` annotations:
the canonical mesh axes are ``('trials', 'features', 'ensemble')``.
"""

from __future__ import annotations

from typing import Any, Generic, Optional, Tuple, TypeVar, Union

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array
ArrayLike = Union[jax.Array, np.ndarray]

_T = TypeVar("_T")


@flax.struct.dataclass
class PaddedArray:
    """A fixed-shape array whose trailing rows/cols are padding.

    ``padded_array`` has the quantized (static) shape. ``is_missing`` holds
    one boolean mask per axis (shape ``[padded_array.shape[i]]``), True where
    that index is padding. ``fill_value`` is what padding positions hold.

    The *unpadded* extent of each axis is a traced value
    (``true_shape``), so growing from 7 to 8 trials inside one padding
    bucket does not retrace.
    """

    padded_array: Array
    is_missing: Tuple[Array, ...]
    fill_value: Any = flax.struct.field(pytree_node=False, default=0.0)

    # -- construction ------------------------------------------------------

    @classmethod
    def from_array(
        cls,
        array: ArrayLike,
        target_shape: Optional[Tuple[int, ...]] = None,
        *,
        fill_value: Any = 0.0,
    ) -> "PaddedArray":
        """Pads ``array`` up to ``target_shape`` (defaults to its own shape).

        Host (numpy) inputs are padded in numpy so only the stable padded
        shape ever reaches the device: a ``jnp.pad`` here would compile one
        program per *unpadded* length (every new trial count) and ship a
        new-shape buffer to the device each suggest.
        """
        on_host = not isinstance(array, jax.Array)
        xp = np if on_host else jnp
        array = xp.asarray(array)
        if on_host and not jax.config.jax_enable_x64:
            # Mirror jax's x64-disabled canonicalization: a float64/int64
            # host buffer would otherwise key a second jit-cache entry per
            # dtype downstream (the exact retrace this host path avoids).
            canonical = {
                np.dtype(np.float64): np.float32,
                np.dtype(np.int64): np.int32,
                np.dtype(np.uint64): np.uint32,
                np.dtype(np.complex128): np.complex64,
            }.get(array.dtype)
            if canonical is not None:
                array = array.astype(canonical)
        if target_shape is None:
            target_shape = array.shape
        if len(target_shape) != array.ndim:
            raise ValueError(f"target_shape {target_shape} rank != array rank {array.ndim}.")
        for axis, (have, want) in enumerate(zip(array.shape, target_shape)):
            if have > want:
                raise ValueError(
                    f"Axis {axis}: array dim {have} exceeds target {want}; cannot pad down."
                )
        pad_width = [(0, want - have) for have, want in zip(array.shape, target_shape)]
        padded = xp.pad(array, pad_width, constant_values=fill_value)
        masks = tuple(
            xp.arange(want) >= have for have, want in zip(array.shape, target_shape)
        )
        return cls(padded_array=padded, is_missing=masks, fill_value=fill_value)

    @classmethod
    def as_padded(cls, array: ArrayLike, *, fill_value: Any = 0.0) -> "PaddedArray":
        """Wraps an array with no padding (all entries valid)."""
        return cls.from_array(array, fill_value=fill_value)

    # -- shape accessors ---------------------------------------------------

    @property
    def shape(self) -> Tuple[int, ...]:
        """The padded (static) shape."""
        return self.padded_array.shape

    @property
    def dtype(self):
        return self.padded_array.dtype

    @property
    def ndim(self) -> int:
        return self.padded_array.ndim

    def true_shape(self) -> Tuple[Array, ...]:
        """Unpadded extent per axis, as traced int32 scalars."""
        return tuple(jnp.sum(~m).astype(jnp.int32) for m in self.is_missing)

    def num_valid(self, axis: int = 0) -> Array:
        return jnp.sum(~self.is_missing[axis]).astype(jnp.int32)

    def valid_mask(self, axis: int = 0) -> Array:
        """True where the index along ``axis`` is real data."""
        return ~self.is_missing[axis]

    def joint_valid_mask(self) -> Array:
        """Full-rank boolean mask, True where every axis index is valid."""
        mask = None
        for axis, m in enumerate(self.is_missing):
            shape = [1] * self.ndim
            shape[axis] = self.shape[axis]
            part = (~m).reshape(shape)
            mask = part if mask is None else mask & part
        assert mask is not None
        return jnp.broadcast_to(mask, self.shape)

    # -- transforms --------------------------------------------------------

    def replace_fill_value(self, fill_value: Any) -> "PaddedArray":
        """Rewrites padding positions to a new fill value."""
        new = jnp.where(self.joint_valid_mask(), self.padded_array, fill_value)
        return PaddedArray(padded_array=new, is_missing=self.is_missing, fill_value=fill_value)

    def unpad(self) -> np.ndarray:
        """Strips padding; host-side only (shape depends on mask values)."""
        counts = [int(np.sum(~np.asarray(m))) for m in self.is_missing]
        out = np.asarray(self.padded_array)
        return out[tuple(slice(0, c) for c in counts)]

    def pad_to(self, target_shape: Tuple[int, ...]) -> "PaddedArray":
        """Re-pads to a larger static shape (host-side convenience)."""
        return PaddedArray.from_array(
            jnp.asarray(self.unpad()), target_shape, fill_value=self.fill_value
        )

    def __repr__(self) -> str:
        return (
            f"PaddedArray(shape={self.shape}, dtype={self.dtype}, "
            f"fill_value={self.fill_value!r})"
        )


@flax.struct.dataclass
class ContinuousAndCategorical(Generic[_T]):
    """A pair of containers, one for continuous and one for categorical data."""

    continuous: _T
    categorical: _T

    def map(self, fn) -> "ContinuousAndCategorical":
        return ContinuousAndCategorical(fn(self.continuous), fn(self.categorical))


# The GP feature container: continuous features are float [N, Dc] scaled to
# [0,1]; categorical features are integer category indices [N, Ds].
ModelInput = ContinuousAndCategorical[PaddedArray]


@flax.struct.dataclass
class ModelData:
    """Features + labels: the training set handed to stochastic-process models."""

    features: ModelInput
    labels: PaddedArray  # [N, num_metrics] float, NaN for infeasible.


def padded_zeros(
    continuous_shape: Tuple[int, int],
    categorical_shape: Tuple[int, int],
    *,
    dtype=jnp.float32,
) -> ModelInput:
    """An all-padding ModelInput (useful as a neutral element)."""
    cont = PaddedArray(
        padded_array=jnp.zeros(continuous_shape, dtype=dtype),
        is_missing=(
            jnp.ones(continuous_shape[0], dtype=bool),
            jnp.ones(continuous_shape[1], dtype=bool),
        ),
        fill_value=0.0,
    )
    cat = PaddedArray(
        padded_array=jnp.zeros(categorical_shape, dtype=jnp.int32),
        is_missing=(
            jnp.ones(categorical_shape[0], dtype=bool),
            jnp.ones(categorical_shape[1], dtype=bool),
        ),
        fill_value=0,
    )
    return ContinuousAndCategorical(continuous=cont, categorical=cat)
