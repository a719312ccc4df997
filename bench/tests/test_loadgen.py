"""The traffic generator: the closed loop's callers and its images."""
import numpy as np
import pytest

import loadgen


def test_closed_loop_only():
    assert loadgen.clients({"loop": "closed", "clients": 8}) == 8
    for loop in ("open", "sideways"):
        with pytest.raises(ValueError):
            loadgen.clients({"loop": loop, "clients": 8})


def test_blob_images_hit_the_live_fraction():
    imgs = loadgen.blob_images(np.random.default_rng(4), 6, 64, 0.446)
    live = (imgs != 0).mean(axis=(1, 2, 3))
    assert imgs.shape == (6, 64, 64, 3) and (imgs >= 0).all()
    assert (live >= 0.446).all() and (live < 0.8).all()


def test_image_pool_repeats_per_seed():
    config = {"input_size": 16, "map_density": 0.5}
    traffic = {"image_pool": 3}
    p1 = loadgen.ImagePool(np.random.default_rng(5), traffic, config)
    p2 = loadgen.ImagePool(np.random.default_rng(5), traffic, config)
    assert p1.images.shape == (3, 16, 16, 3)
    for _ in range(10):
        assert (p1.image() == p2.image()).all()
