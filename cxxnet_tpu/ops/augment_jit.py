"""Device-side image augmentation: crop / mirror / mean / scale inside
the jitted train step.

The reference augments every image on the HOST (image_augmenter-inl.hpp
+ the crop/mirror/mean pipeline of iter_img_proc). That is the right
call for GPUs with idle host cores; on a TPU host where a single b256
AlexNet batch costs tens of ms of numpy arithmetic per step, the host
becomes the bottleneck while the MXU idles. `device_augment = 1`
moves the per-pixel work onto the device, TPU-style:

- the iterator stages RAW decoded images (io/augment.py passthrough
  mode; uint8 batches ride H2D at 1/4 the f32 bytes);
- the jitted step crops FIRST (per-sample jax.random offsets via
  vmapped dynamic_slice - O(crop) arithmetic, not O(raw)), subtracts
  the mean, applies contrast/illumination draws, mirrors by a
  per-sample flag, scales, and casts to the compute dtype - all fused
  by XLA into the step's leading ops;
- eval/predict use the deterministic variant (center crop, no mirror,
  no jitter), matching AugmentIterator's non-random path.

Semantics parity with io/augment.py `_set_data` (the host pipeline):
(x - mean) * contrast + illumination, crop, mirror, * scale - with the
crop commuted ahead of the (elementwise) subtraction, and the mirror
applied to the difference, exactly as the host path does. The mean
image may be crop-sized (what `_create_mean_img` produces - it
accumulates processed, i.e. cropped, instances) or raw-sized (a
user-provided full-frame mean): crop-sized subtracts directly,
raw-sized is cropped per-sample with the same offsets.

Randomness comes from the step PRNG instead of the iterator's numpy
RandomState - a documented deviation: same distributions, different
stream (the reference seeds per-iterator, we fold per-step).

Affine warps (rotation/shear/aspect/random-scale) are NOT deferrable -
they run scipy on the host - so passthrough mode rejects them
(io/augment.py validates ImageAugmenter.need_process() == False).
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

Shape3 = Tuple[int, int, int]


def make_device_augment(out_shape: Shape3,
                        mean_loader: Optional[Callable] = None,
                        mean_values: Optional[Tuple[float, float, float]]
                        = None,
                        scale: float = 1.0,
                        rand_crop: int = 0, rand_mirror: int = 0,
                        mirror: int = 0,
                        crop_y_start: int = -1, crop_x_start: int = -1,
                        max_random_contrast: float = 0.0,
                        max_random_illumination: float = 0.0,
                        ) -> Callable:
    """Build `apply(data, rng, train) -> (b, c, ty, tx) float32`.

    out_shape: the net's (c, ty, tx) input_shape. The RAW staged shape
    is read from the traced batch at trace time (no config key needed).
    mean_loader: nullary callable returning the (c, ry, rx)- or
    (c, ty, tx)-shaped f32 mean array (or None) - called lazily at
    trace time, AFTER the iterator had its chance to create the mean
    file on first use. When both are configured, mean_values wins and
    the mean image is never loaded - the host pipeline's precedence
    (io/augment.py:313 checks the per-channel values first).
    """
    c, ty, tx = out_shape
    if mean_values is not None and not any(mean_values):
        # all-zero mean_value is OFF on the host path (the branch tests
        # mean_r/g/b > 0), which also disables contrast/illumination
        mean_values = None
    if mean_values is not None:
        mean_loader = None

    def apply(data, rng, train: bool):
        b, dc, ry, rx = data.shape
        if dc != c or ty > ry or tx > rx:
            raise ValueError(
                f"device_augment: raw batch {data.shape[1:]} cannot "
                f"produce net input {out_shape}")
        meanimg = mean_loader() if mean_loader is not None else None
        if meanimg is not None and meanimg.shape not in (
                (c, ry, rx), (c, ty, tx)):
            raise ValueError(
                f"device_augment: mean image {meanimg.shape} matches "
                f"neither the raw shape {(c, ry, rx)} nor the crop "
                f"shape {(c, ty, tx)}")
        yy_max, xx_max = ry - ty, rx - tx

        k_y, k_x, k_m, k_c, k_i = jax.random.split(rng, 5)
        if train and rand_crop and (yy_max or xx_max):
            yy = jax.random.randint(k_y, (b,), 0, yy_max + 1)
            xx = jax.random.randint(k_x, (b,), 0, xx_max + 1)
        else:
            yy = jnp.full((b,), yy_max // 2, jnp.int32)
            xx = jnp.full((b,), xx_max // 2, jnp.int32)
        # fixed crop offsets (crop_y/x_start) override BOTH the center
        # default and a random draw, exactly like the host path
        # (augment.py applies them after the rand_crop branch). Range-
        # check here: dynamic_slice CLAMPS out-of-range offsets, which
        # would silently train on shifted windows where the host path
        # fails on the resulting shape mismatch
        if yy_max and crop_y_start != -1:
            if not 0 <= crop_y_start <= yy_max:
                raise ValueError(
                    f"device_augment: crop_y_start={crop_y_start} out "
                    f"of range [0, {yy_max}] for raw {ry} crop {ty}")
            yy = jnp.full((b,), crop_y_start, jnp.int32)
        if xx_max and crop_x_start != -1:
            if not 0 <= crop_x_start <= xx_max:
                raise ValueError(
                    f"device_augment: crop_x_start={crop_x_start} out "
                    f"of range [0, {xx_max}] for raw {rx} crop {tx}")
            xx = jnp.full((b,), crop_x_start, jnp.int32)
        if train and rand_mirror:
            # mirror=1 still forces EVERY sample - the host path ORs
            # the flags (io/augment.py:309-310), it does not let the
            # random draw override the unconditional mirror
            mir = jax.random.bernoulli(k_m, 0.5, (b,))
            if mirror:
                mir = jnp.ones((b,), bool)
        else:
            mir = jnp.full((b,), bool(mirror))
        # host-pipeline parity quirk: contrast/illumination only apply
        # on the mean-subtracting branches (augment.py's no-mean branch
        # crops without them) - match it, never "fix" it silently
        has_mean = mean_loader is not None or mean_values is not None
        if train and max_random_contrast > 0 and has_mean:
            contrast = 1.0 + jax.random.uniform(
                k_c, (b,), minval=-max_random_contrast,
                maxval=max_random_contrast)
        else:
            contrast = jnp.ones((b,), jnp.float32)
        if train and max_random_illumination > 0 and has_mean:
            illum = jax.random.uniform(
                k_i, (b,), minval=-max_random_illumination,
                maxval=max_random_illumination)
        else:
            illum = jnp.zeros((b,), jnp.float32)

        mean_c = (jnp.asarray(meanimg, jnp.float32)
                  if meanimg is not None else None)
        raw_mean = mean_c is not None and mean_c.shape == (c, ry, rx)

        def one(img, yy, xx, mir, contrast, illum):
            x = jax.lax.dynamic_slice(
                img, (0, yy, xx), (c, ty, tx)).astype(jnp.float32)
            if mean_values is not None:
                # host precedence: per-channel values beat the mean
                # image (augment.py:313; subtraction only at c == 3,
                # but contrast/illumination apply regardless)
                if c == 3:
                    mb, mg, mr = mean_values
                    x = x - jnp.asarray([mr, mg, mb],
                                        jnp.float32)[:, None, None]
            elif mean_c is not None:
                # crop-then-subtract == subtract-then-crop (elementwise)
                m = (jax.lax.dynamic_slice(mean_c, (0, yy, xx),
                                           (c, ty, tx))
                     if raw_mean else mean_c)
                x = x - m
            x = x * contrast + illum
            # mirror AFTER the subtraction (the host path mirrors the
            # mean-subtracted crop, not the raw pixels)
            x = jnp.where(mir, x[:, :, ::-1], x)
            return x * scale

        return jax.vmap(one)(data, yy, xx, mir, contrast, illum)

    return apply
