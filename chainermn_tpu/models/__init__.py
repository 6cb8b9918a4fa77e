"""Model zoo (reference example model families, TPU-first designs)."""

from .mlp import MLP, Classifier
from .resnet import (ResNet, ResNet18, ResNet50, ResNet101,
                     BottleneckBlock, BasicBlock)
from .seq2seq import (Seq2seq, Encoder, Decoder, ModelParallelSeq2seq,
                      create_model_parallel_seq2seq,
                      make_synthetic_translation_data)
from .dcgan import Generator, Discriminator, DCGANUpdater
from .transformer import TransformerLM, TransformerBlock, MultiHeadAttention
from .moe_transformer import (MoETransformerLM, MoETransformerBlock,
                              MoEFeedForward)
from .latent_moe import LatentMoELM, LatentMoEBlock, LatentAttention
from .window_moe import (WindowMoELM, WindowMoEBlock,
                         GatedGroupedAttention)
from .prerouted_moe import (PreroutedMoELM, PreroutedMoEBlock,
                            GroupedAttention)
from .hybrid_delta import (HybridDeltaLM, HybridDeltaBlock, DeltaMixer,
                           FullMixer)
from .looped import LoopedLM, LoopedBlock, LoopedAttention
from .convnets import AlexNet, NIN, VGG16, GoogLeNet

__all__ = ["MLP", "Classifier", "ResNet", "ResNet18", "ResNet50",
           "ResNet101", "BottleneckBlock", "BasicBlock", "Seq2seq",
           "Encoder", "Decoder", "ModelParallelSeq2seq",
           "create_model_parallel_seq2seq",
           "make_synthetic_translation_data", "Generator", "Discriminator",
           "DCGANUpdater", "TransformerLM", "TransformerBlock",
           "MultiHeadAttention", "MoETransformerLM", "MoETransformerBlock",
           "MoEFeedForward", "LatentMoELM", "LatentMoEBlock",
           "LatentAttention", "WindowMoELM", "WindowMoEBlock",
           "GatedGroupedAttention", "PreroutedMoELM", "PreroutedMoEBlock",
           "GroupedAttention", "HybridDeltaLM", "HybridDeltaBlock",
           "DeltaMixer", "FullMixer", "LoopedLM", "LoopedBlock",
           "LoopedAttention", "AlexNet", "NIN", "VGG16", "GoogLeNet"]
