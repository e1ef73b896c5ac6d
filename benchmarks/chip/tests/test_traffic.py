"""The generators, found by the names a mix gives them."""
import numpy as np

from harness import spec, traffic
from rehearse import tiny


def test_seeds_offer_the_same_sizes():
    cell = spec.load_cell("imdb-serve")
    gen = traffic.generator(cell.traffic)
    a, b = (gen.schedule(cell.traffic, cell.config, 16.0, 200.0, s)
            for s in (2 ** 33 + 1, 7))
    n = cell.traffic["block"]
    assert sorted(a.frames[:n]) == sorted(b.frames[:n])
    assert not np.array_equal(a.frames[:n], b.frames[:n])
    assert a.pool.shape[1:] == (cell.config["layer_sizes"][0],)
    assert (a.offsets + a.frames <= len(a.pool)).all()


def test_frame_shape_follows_the_configuration():
    cell = tiny(spec.load_cell("imdb-serve"))
    mnist = spec.load_cell("mnist-batch").config
    sched = traffic.generator(cell.traffic).schedule(
        cell.traffic, mnist, 4.0, 2.0, 3)
    assert sched.pool.shape[1:] == (28, 28, 1)
    assert traffic.frame_shape(cell.config) == (100,)
