"""Work counts against hand arithmetic."""
import pytest

from harness import spec
from harness.work import macro_layers, macs_per_lane_frame, required


@pytest.fixture(scope="module")
def configs():
    bench = spec.load_cell("imdb-batch"), spec.load_cell("mnist-batch")
    return {c.config_name: c.config for c in bench}


def test_macs_per_lane_frame(configs):
    assert macs_per_lane_frame(configs["impulse-imdb"]) == \
        100 * 128 + 128 * 128 + 128 * 1 == 29_312
    assert macs_per_lane_frame(configs["impulse-mnist"]) == \
        196 * 126 * 14 + 49 * 126 * 14 + 686 * 120 + 120 * 84 + 84 * 10 \
        == 525_420


def test_imdb_bytes(configs):
    b, t = 1024, 2560
    ops, nbytes = required(configs["impulse-imdb"], b, t)
    assert ops == 2 * 29_312 * b * t
    spikes_in = b * t * 100                       # int8, once
    w = 100 * 128 + 128 * 128 + 128 * 1           # int8, once
    v = b * 128 * 4 + b * 128 * 4 + b * 1 * 4     # int32 final V
    logits = b * 1 * 4                            # float32
    assert nbytes == spikes_in + w + v + logits == 263_230_080


def test_mnist_bytes(configs):
    b, t = 1024, 10
    ops, nbytes = required(configs["impulse-mnist"], b, t)
    assert ops == 2 * 525_420 * b * t
    spikes_in = b * t * 28 * 28 * 14      # the encoder's spike maps, int8
    w = 126 * 14 * 2 + 686 * 120 + 120 * 84 + 84 * 10
    v = b * 4 * (14 * 14 * 14 + 7 * 7 * 14 + 120 + 84 + 10)
    logits = b * 10 * 4
    assert nbytes == spikes_in + w + v + logits == 127_457_792


def test_layers_are_logical(configs):
    """No padded width: every fan-in and fan-out is the model's own."""
    widths = {(ly["n_in"], ly["n_out"])
              for ly in macro_layers(configs["impulse-mnist"])}
    assert widths == {(126, 14), (686, 120), (120, 84), (84, 10)}
