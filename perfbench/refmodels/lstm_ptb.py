"""The "medium" regularised LSTM of Zaremba et al. (arXiv:1409.2329, 4.1).

Embedding 10,000 x 650, two LSTM layers of 650 units unrolled over 35
tokens, dropout 0.5 on the non-recurrent connections (after the embedding
and after each layer), a 650 x 10,000 softmax; the hidden state is carried
from one window to the next and not differentiated through. Departures, as
the configuration file states them: the output projection is not tied to
the embedding (19.8M parameters, as the paper's medium model has), and the
matrix products run in ``dtype`` with float32 parameters.

The module names fix the parameter names and the dropout streams: each
Dropout draws from the key folded with its own path.
"""

import flax.linen as nn
import jax.numpy as jnp
import optax


class LSTMLM(nn.Module):
    vocab_size: int
    hidden_size: int
    num_layers: int
    dropout_rate: float
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, tokens, carry, train):
        x = nn.Embed(self.vocab_size, self.hidden_size, dtype=self.dtype)(tokens)
        x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        new_carry = []
        for layer in range(self.num_layers):
            rnn = nn.RNN(nn.OptimizedLSTMCell(self.hidden_size, dtype=self.dtype),
                         return_carry=True)
            c, x = rnn(x, initial_carry=carry[layer])
            new_carry.append(c)
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        logits = nn.Dense(self.vocab_size, dtype=self.dtype)(x)
        return logits.astype(jnp.float32), tuple(new_carry)


def build(sizes, dtype):
    module = LSTMLM(sizes["vocab_size"], sizes["hidden_size"],
                    sizes["num_layers"], sizes["dropout_rate"], dtype)
    return module, jnp.zeros((1, sizes["bptt"]), jnp.int32)


def initial_carry(sizes, batch, dtype):
    zeros = lambda: jnp.zeros((batch, sizes["hidden_size"]), dtype)
    return tuple((zeros(), zeros()) for _ in range(sizes["num_layers"]))


def loss(module, variables, carry, batch, key, train):
    logits, new_carry = module.apply(
        variables, batch["tokens"], carry, train,
        rngs={"dropout": key} if train else None)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits, batch["targets"]).mean()
    return ce, None, new_carry


def forward_macs(sizes):
    """Multiply-accumulates of one window's forward pass, from the shapes:
    four gates per layer over input and hidden state, and the softmax's
    projection (the embedding is a lookup)."""
    h = sizes["hidden_size"]
    per_token = sizes["num_layers"] * 4 * (h + h) * h + h * sizes["vocab_size"]
    return per_token * sizes["bptt"]
