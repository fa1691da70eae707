"""LSTM sentiment classifier — the reference's IMDB workload (BASELINE config #4).

TPU notes: with ``cell_impl="xla"`` the recurrence is a ``lax.scan`` (via
``nn.RNN``) over static-length sequences. That lowering pays per-timestep
device while-loop overhead (~35-45us measured in round 4 on an earlier
single-chip setup) — more than the tiny cell matmul itself —
so ``cell_impl="pallas"`` runs the whole sequence as ONE Pallas program
(``ops/pallas/lstm.py``): weights pinned in VMEM across timesteps, BPTT as a
reversed-grid kernel. Both implement flax ``OptimizedLSTMCell`` math exactly
(equivalence-tested); they differ only in param layout (packed vs per-gate —
``pack_lstm_params`` converts).
"""

from __future__ import annotations

import jax.numpy as jnp
from flax import linen as nn

from distkeras_tpu.models.base import DKModule, Model, register_model
from distkeras_tpu.ops.pallas.lstm import _orthogonal_gates, lstm_seq


@register_model
class LSTMClassifier(DKModule):
    vocab_size: int = 20000
    embed_dim: int = 128
    hidden_size: int = 128
    num_outputs: int = 2
    dropout_rate: float = 0.0
    cell_impl: str = "xla"  # "xla" (nn.RNN scan) | "pallas" (one-kernel seq)

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        # tokens: [batch, seq] int32
        x = nn.Embed(self.vocab_size, self.embed_dim)(tokens)
        if self.cell_impl == "pallas":
            E, H = self.embed_dim, self.hidden_size
            wx = self.param("lstm_wx", nn.initializers.lecun_normal(), (E, 4 * H))
            wh = self.param("lstm_wh", _orthogonal_gates, (H, 4 * H))
            b = self.param("lstm_b", nn.initializers.zeros, (4 * H,))
            if self.is_initializing():
                # init only declares params; don't trace the kernel (it may
                # not lower on the init device, e.g. CPU-pinned param init)
                x = jnp.zeros(x.shape[:-1] + (H,), x.dtype)
            else:
                x = lstm_seq(wx.astype(x.dtype), wh.astype(x.dtype),
                             b.astype(x.dtype), x)
        else:
            x = nn.RNN(nn.OptimizedLSTMCell(self.hidden_size))(x)
        x = x[:, -1, :]  # last hidden state
        if self.dropout_rate > 0.0:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        return nn.Dense(self.num_outputs)(x)


def imdb_lstm(
    vocab_size: int = 20000,
    embed_dim: int = 128,
    hidden_size: int = 128,
    seq_len: int = 80,
    seed: int = 0,
    cell_impl: str = "xla",
) -> Model:
    module = LSTMClassifier(
        vocab_size=vocab_size, embed_dim=embed_dim, hidden_size=hidden_size,
        num_outputs=2, cell_impl=cell_impl,
    )
    return Model.build(module, jnp.zeros((1, seq_len), jnp.int32), seed=seed)
