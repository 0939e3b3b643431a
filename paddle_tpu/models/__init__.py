"""Model zoo built purely from fluid-style layers — the acceptance configs of
BASELINE.json (MNIST LeNet, ResNet-50, VGG, Transformer NMT, DeepFM CTR,
stacked-LSTM LM), mirroring reference benchmark/fluid/models/."""

from . import alexnet, block_decoder, googlenet, gpt_decoder, lenet, resnet, se_resnext, vgg
from .block_decoder import BlockDecoder, lfm2_moe
from .gpt_decoder import GPTDecoder
from .lenet import lenet5
from .resnet import resnet50, resnet_cifar10
from .alexnet import alexnet as alexnet_model
from .googlenet import googlenet as googlenet_model
from .se_resnext import se_resnext50
from .vgg import vgg16
