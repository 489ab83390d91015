"""BERT model family — BASELINE config 3 (BERT-base fine-tune).

Reference parity: the transformer encoder stack the reference builds from
nn.MultiHeadAttention / TransformerEncoderLayer (reference
python/paddle/nn/layer/transformer.py:132/:568) as consumed by PaddleNLP's
BertModel.  Imperative ``Layer`` graph; fine-tuning runs under the hapi
trainer or DistributedEngine (dp/sharding).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.attr import ParamAttr
from ..nn.layer.activation import Tanh
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm
from ..nn.layer.transformer import TransformerEncoder, TransformerEncoderLayer

__all__ = ["BertConfig", "BertEmbeddings", "BertPooler", "BertModel",
           "BertForSequenceClassification", "BertForPretraining",
           "bert_tiny", "bert_base", "bert_large"]


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0


def bert_tiny(**kw) -> BertConfig:
    kw.setdefault("vocab_size", 256)
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("intermediate_size", 128)
    kw.setdefault("max_position_embeddings", 64)
    return BertConfig(**kw)


def bert_base(**kw) -> BertConfig:
    return BertConfig(**kw)


def bert_large(**kw) -> BertConfig:
    kw.setdefault("hidden_size", 1024)
    kw.setdefault("num_layers", 24)
    kw.setdefault("num_heads", 16)
    kw.setdefault("intermediate_size", 4096)
    return BertConfig(**kw)


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        attr = ParamAttr(initializer=I.Normal(0.0, cfg.initializer_range))
        self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=attr)
        self.position_embeddings = Embedding(cfg.max_position_embeddings,
                                             cfg.hidden_size,
                                             weight_attr=attr)
        self.token_type_embeddings = Embedding(cfg.type_vocab_size,
                                               cfg.hidden_size,
                                               weight_attr=attr)
        self.layer_norm = LayerNorm(cfg.hidden_size,
                                    epsilon=cfg.layer_norm_eps)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None):
        from ..ops import api as _api
        s = input_ids.shape[1]
        pos = _api.arange(0, s, 1, dtype="int64")
        x = self.word_embeddings(input_ids) + self.position_embeddings(pos)
        if token_type_ids is None:
            token_type_ids = _api.zeros_like(input_ids)
        x = x + self.token_type_embeddings(token_type_ids)
        return self.dropout(self.layer_norm(x))


class BertPooler(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.dense = Linear(cfg.hidden_size, cfg.hidden_size)
        self.activation = Tanh()

    def forward(self, hidden):
        return self.activation(self.dense(hidden[:, 0]))


class BertModel(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout_prob, activation=cfg.hidden_act,
            attn_dropout=cfg.attention_probs_dropout_prob,
            act_dropout=0.0, layer_norm_eps=cfg.layer_norm_eps)
        self.encoder = TransformerEncoder(enc_layer, cfg.num_layers)
        self.pooler = BertPooler(cfg)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        from ..ops import api as _api
        if attention_mask is not None and len(attention_mask.shape) == 2:
            # [b, s] pad mask -> additive [b, 1, 1, s]
            m = _api.cast(attention_mask, "float32")
            attention_mask = (m - 1.0) * 1e9
            attention_mask = _api.reshape(
                attention_mask, [m.shape[0], 1, 1, m.shape[1]])
        x = self.embeddings(input_ids, token_type_ids)
        x = self.encoder(x, src_mask=attention_mask)
        return x, self.pooler(x)


class BertForSequenceClassification(Layer):
    """Fine-tune head — the BERT-base baseline config."""

    def __init__(self, cfg: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(cfg)
        self.dropout = Dropout(cfg.hidden_dropout_prob)
        self.classifier = Linear(cfg.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                labels=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            return F.cross_entropy(logits, labels)
        return logits


class BertForPretraining(Layer):
    """MLM + NSP heads (tied MLM decoder)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size)
        self.transform_ln = LayerNorm(cfg.hidden_size,
                                      epsilon=cfg.layer_norm_eps)
        self.nsp_head = Linear(cfg.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                mlm_labels=None, nsp_labels=None):
        from ..ops import api as _api
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        h = self.transform_ln(F.gelu(self.transform(seq)))
        mlm_logits = _api.matmul(h, self.bert.embeddings.word_embeddings.weight,
                                 transpose_y=True)
        nsp_logits = self.nsp_head(pooled)
        if mlm_labels is not None:
            mlm_loss = F.cross_entropy(
                _api.reshape(mlm_logits, [-1, self.cfg.vocab_size]),
                _api.reshape(mlm_labels, [-1]), ignore_index=-100)
            loss = mlm_loss
            if nsp_labels is not None:
                loss = loss + F.cross_entropy(nsp_logits, nsp_labels)
            return loss
        return mlm_logits, nsp_logits
