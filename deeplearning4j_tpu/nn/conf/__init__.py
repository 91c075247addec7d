"""Configuration package — TPU equivalent of reference `nn/conf/`."""

from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: F401
from deeplearning4j_tpu.nn.conf.layers import (  # noqa: F401
    ActivationLayer,
    AutoEncoder,
    BatchNormalization,
    ConvolutionLayer,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    GlobalPoolingLayer,
    GravesBidirectionalLSTM,
    GravesLSTM,
    Layer,
    LocalResponseNormalization,
    LossLayer,
    OutputLayer,
    RBM,
    RMSNormalization,
    RnnOutputLayer,
    SelfAttention,
    SubsamplingLayer,
    TiedRnnOutputLayer,
)
from deeplearning4j_tpu.nn.conf.decoder_block import (  # noqa: F401
    ChannelGatedDeltaMixer,
    AttentionMixer,
    DecoderBlock,
    LatentAttentionMixer,
    Mamba2Mixer,
    MoEFeedForward,
    RMSNorm,
    ShortcutDecoderBlock,
    YarnScaling,
)
from deeplearning4j_tpu.nn.conf.variational import (  # noqa: F401
    BernoulliReconstructionDistribution,
    CompositeReconstructionDistribution,
    ExponentialReconstructionDistribution,
    GaussianReconstructionDistribution,
    LossFunctionWrapper,
    ReconstructionDistribution,
    VariationalAutoencoder,
)
from deeplearning4j_tpu.nn.conf.neural_net_configuration import (  # noqa: F401
    GlobalConf,
    ListBuilder,
    MultiLayerConfiguration,
    NeuralNetConfiguration,
    OptimizationAlgorithm,
)
from deeplearning4j_tpu.util.conv_utils import ConvolutionMode, PoolingType  # noqa: F401


def __getattr__(name):
    # lazy: ComputationGraphConfiguration lives in its own module and is
    # imported on demand to keep the MLN-only path light
    if name in ("ComputationGraphConfiguration", "GraphBuilder"):
        from deeplearning4j_tpu.nn.conf import computation_graph_configuration as m

        return getattr(m, name)
    raise AttributeError(name)
