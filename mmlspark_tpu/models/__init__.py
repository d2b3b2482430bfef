from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101
from .bilstm import BiLSTMTagger, LSTMLayer
from .transformer import TransformerEncoder, EncoderBlock, MultiHeadAttention
from .sparse_moe import SparseMoEDecoder
from .gbdt import GBDTBooster
from .runner import (ModelRunner, DecodeResult, PagePool,
                     ContinuousDecoder, StreamHandle, PagePoolExhausted,
                     SlotsExhausted, ShedReply, RowSource, bucket_rows)

__all__ = ["ResNet", "resnet18", "resnet34", "resnet50", "resnet101",
           "BiLSTMTagger", "LSTMLayer", "TransformerEncoder", "EncoderBlock",
           "MultiHeadAttention", "SparseMoEDecoder", "GBDTBooster", "ModelRunner", "DecodeResult",
           "PagePool", "ContinuousDecoder", "StreamHandle",
           "PagePoolExhausted", "SlotsExhausted", "ShedReply", "RowSource",
           "bucket_rows"]
