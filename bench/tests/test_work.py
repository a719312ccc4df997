"""The work functions: MACs, FLOPs, bytes and the roofline time."""
import pytest

import spec
import weights as W
import work


def _config(name):
    return spec.load_json(f"{spec.BENCH}/configs/{name}.json")


def _dense_nonzero(config):
    return [l["kernel"] ** 2 * l["in_channels"] * l["out_channels"]
            for l in config["layers"]]


def test_vgg16_macs_per_image():
    config = _config("vgg16_224")
    dense = work.table_work(config, 224, _dense_nonzero(config))
    assert [l.out_hw for l in dense] == [224, 224, 112, 112, 56, 56, 56,
                                         28, 28, 28, 14, 14, 14]
    total = sum(l.dense_macs for l in dense)
    assert total == 15_346_630_656        # 15.35 GMAC, conv layers only
    assert all(l.macs == l.dense_macs for l in dense)

    filters = W.make_weights(config, 0)
    nz = work.nonzero_counts(filters)
    keep = [W.kept_per_filter(f.shape, config["filter_density"]) * f.shape[3]
            for f in filters]
    assert nz == keep                     # exact per-filter pruning
    pruned = work.table_work(config, 224, nz)
    macs = sum(l.macs for l in pruned)
    assert macs == pytest.approx(0.334 * total, rel=2e-3)   # 5.13 GMAC
    assert work.flops_per_image(pruned) == 2 * macs


def test_alexnet_macs_per_image():
    config = _config("alexnet_227")
    dense = work.table_work(config, 227, _dense_nonzero(config))
    assert [l.out_hw for l in dense] == [55, 27, 13, 13, 13]
    assert [l.in_hw for l in dense] == [227, 27, 13, 13, 13]
    assert sum(l.dense_macs for l in dense) == 1_076_634_144   # 1.08 GMAC


def test_bytes_and_roofline_of_one_layer():
    layer = work.LayerWork(in_hw=4, out_hw=2, cin=3, cout=5, kernel=3,
                           nonzero=20)
    assert layer.macs == 4 * 20
    assert layer.flops(2) == 2 * 80 * 2
    # two images' maps (4*4*3 in + 2*2*5 out) plus the weights once
    assert layer.bytes(2) == 4 * (2 * (48 + 20) + 20)
    compute_bound = work.roofline_seconds([layer], 2, peak_flops=1.0,
                                          peak_bytes_s=1e9)
    assert compute_bound == layer.flops(2)
    memory_bound = work.roofline_seconds([layer], 2, peak_flops=1e12,
                                         peak_bytes_s=1.0)
    assert memory_bound == layer.bytes(2)


def test_weights_repeat_per_seed_and_take_large_seeds():
    config = _config("alexnet_227")
    a = W.make_weights(config, 2 ** 31 + 5)
    b = W.make_weights(config, 2 ** 31 + 5)
    c = W.make_weights(config, 2 ** 31 + 6)
    assert all((x == y).all() for x, y in zip(a, b))
    assert any((x != y).any() for x, y in zip(a, c))
    with pytest.raises(ValueError):
        W.seed_words(-1)
