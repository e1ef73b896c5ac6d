"""The paper's own workloads: IMDB sentiment SNN and MNIST LeNet5-mod SNN.

These are not LM registry entries; they configure the core/ spiking stack.
  impulse-imdb : input 100 (GloVe-100d spike encoder) -> FC128 -> FC128 -> 1,
                 RMP neurons, 6b W / 11b V_MEM, 10 timesteps. 29.3K params.
  impulse-mnist: modified LeNet-5 with fan-in <= 128 (14 input channels, 3x3
                 kernels => 3*3*14 = 126 <= 128), FC layers < 128 neurons.
  rsnn-ssc     : two-layer recurrent SNN for Spiking Speech Commands
                 (arXiv:2103.12593 topology, arXiv:1910.07407 data):
                 700 cochlea channels -> 400r -> 400r -> 35, RMP neurons,
                 6b W / 11b V_MEM, one macro timestep per 4 ms input bin.
"""
from dataclasses import dataclass, field

from repro.configs.base import SpikingConfig


@dataclass(frozen=True)
class SNNModelConfig:
    arch_id: str
    layer_sizes: tuple            # FC sizes, input first
    conv_spec: tuple = ()         # ((out_ch, k, stride), ...) before FC stack
    in_shape: tuple = ()          # conv input (H, W, C)
    spiking: SpikingConfig = field(default_factory=SpikingConfig)
    timesteps: int = 10
    task: str = "binary"          # binary | multiclass
    # per hidden FC layer: True makes it recurrent — its previous-tick
    # spikes feed back through n_out further W_MEM rows (DESIGN.md §3
    # "Recurrent layers"); () = all feed-forward
    recurrent: tuple = ()

    def is_recurrent(self, j: int) -> bool:
        """Whether hidden FC layer ``j`` (0 = the first after the input)
        is recurrent."""
        return j < len(self.recurrent) and bool(self.recurrent[j])


IMDB = SNNModelConfig(
    arch_id="impulse-imdb",
    layer_sizes=(100, 128, 128, 1),
    spiking=SpikingConfig(neuron="rmp", timesteps=10, threshold=1.0,
                          leak=0.0625, w_bits=6, v_bits=11),
    timesteps=10,
    task="binary",
)

# Modified LeNet-5: Conv1 is the spike encoder (kept off-macro, like the paper's
# input layer); Conv2,3 + FC1,2 are mapped on IMPULSE. Channel counts chosen so
# fan-in = 3*3*14 = 126 <= 128 and FC neurons < 128, per the paper.
MNIST = SNNModelConfig(
    arch_id="impulse-mnist",
    conv_spec=((14, 3, 1), (14, 3, 2), (14, 3, 2)),   # encoder + 2 macro convs
    in_shape=(28, 28, 1),
    layer_sizes=(686, 120, 84, 10),                   # 7*7*14 = 686 flatten
    spiking=SpikingConfig(neuron="rmp", timesteps=10, threshold=1.0,
                          leak=0.0625, w_bits=6, v_bits=11),
    timesteps=10,
    task="multiclass",
)

# Two recurrent hidden layers of 400 (Yin, Corradi & Bohte 2021, their SSC
# SRNN) on the macro's own RMP neuron and accumulate readout: W_rec is 400
# further W_MEM rows after each layer's input rows, on the same 6-bit grid.
RSNN_SSC = SNNModelConfig(
    arch_id="rsnn-ssc",
    layer_sizes=(700, 400, 400, 35),
    spiking=SpikingConfig(neuron="rmp", timesteps=1, threshold=1.0,
                          leak=0.0625, w_bits=6, v_bits=11),
    timesteps=1,
    task="multiclass",
    recurrent=(True, True),
)

SNN_CONFIGS = {c.arch_id: c for c in (IMDB, MNIST, RSNN_SSC)}


def get_snn_config(arch_id: str) -> SNNModelConfig:
    return SNN_CONFIGS[arch_id]
