"""The task of the language-model configurations: rows of packed token ids,
next-token cross-entropy over a slice of the vocabulary plus, where the model
has a multi-token-prediction module, that module's cross-entropy weighed by
``MTP_WEIGHT``. A sample, in ``samples_per_s_per_chip``, is one row: one
packed sequence of ``seq_len`` tokens.

``make_data`` lays the clients' corpora out in turn (a traffic file with
``"shards": "contiguous"`` deals client ``c`` the ``c``-th run of rows): each
client draws its ids Zipf(``zipf_exponent``) over its OWN seeded permutation
of the vocabulary slice (so the clients' frequent tokens, and with them the
load on the held experts, differ), in documents of log-normal length (median
``doc_median``, longest ``doc_longest``) that end in the end-of-document id 0
and are packed end to end into rows of ``seq_len``; a row starts where the
last ended, mid-document as a rule. ``targets`` are the ids moved left by one,
-1 at a row's end: no target.

The reference's forward gives ``(logits, further...)``, head ``d`` predicting
``d + 1`` tokens on; each head's cross-entropy is the mean over its own valid
positions (a position whose target lies past the row's end is not valid).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MTP_WEIGHT = 0.3  # assumed: the config gives no weight (configs/*.json, "assumed")
EOD = 0  # the end-of-document id


def make_data(seed, cfg):
    """``(ids [n, T] int32, targets [n, T] int32)`` as host arrays, ``n =
    clients_corpora * rows_per_client`` in the clients' order."""
    from benchmark import seeded

    t, vocab = cfg["seq_len"], cfg["vocab_size"]
    rows, corpora = cfg["rows_per_client"], cfg["clients_corpora"]
    sigma = np.log(cfg["doc_longest"] / cfg["doc_median"]) / 3.0
    rank_p = 1.0 / np.arange(1, vocab, dtype=np.float64) ** cfg["zipf_exponent"]
    rank_p /= rank_p.sum()
    # A host generator seeded from the run's key stream (stream 1, as every
    # task): documents of varying length are a host's business.
    rng = np.random.default_rng(np.asarray(seeded.key_of(seed, 1), np.uint32))
    ids = np.empty((corpora * rows, t), np.int32)
    for c in range(corpora):
        own = 1 + rng.permutation(vocab - 1)  # rank -> id, never the EOD id
        need, docs = rows * t, []
        while need > 0:
            n = int(np.clip(np.exp(rng.normal(np.log(cfg["doc_median"]), sigma)),
                            2, cfg["doc_longest"]))
            docs.append(own[rng.choice(vocab - 1, size=n - 1, p=rank_p)])
            docs.append(np.array([EOD]))
            need -= n
        ids[c * rows:(c + 1) * rows] = np.concatenate(docs)[:rows * t].reshape(rows, t)
    targets = np.concatenate(
        [ids[:, 1:], np.full((len(ids), 1), -1, np.int32)], axis=1)
    return ids, targets


def _head_parts(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = targets >= 0
    picked = jnp.take_along_axis(logp, jnp.where(valid, targets, 0)[..., None], axis=-1)
    return -jnp.sum(jnp.where(valid, picked[..., 0], 0.0)), jnp.sum(valid)


def _heads(logits, targets):
    """``[(weight, sum, count)]`` a head; head ``d``'s targets lie ``d``
    further left."""
    out = []
    for d, head in enumerate(logits if isinstance(logits, tuple) else (logits,)):
        moved = jnp.concatenate(
            [targets[..., d:], jnp.full(targets.shape[:-1] + (d,), -1, targets.dtype)],
            axis=-1)
        out.append((MTP_WEIGHT if d else 1.0,) + _head_parts(head, moved))
    return out


def loss(logits, targets):
    """The reference's loss of a whole batch, float32."""
    return sum(w * s / n for w, s, n in _heads(logits, targets))


def loss_parts(logits, targets):
    """``(sum, count)`` with ``loss == sum / count`` for a step taken in
    blocks of rows. Every row has the same number of valid positions a head
    (``T - 1 - d``), so a block's heads share one count up to that known
    ratio: the sum is scaled to the next-token head's count."""
    heads = _heads(logits, targets)
    count = heads[0][2]
    return sum(w * s * (count / n) for w, s, n in heads), count.astype(jnp.float32)


def program_fields(cfg):
    """The fields of the program's configuration that state this kind of
    data, by dataclass field name."""
    return {
        "round": {"num_classes": cfg["vocab_size"], "image_size": (cfg["seq_len"],)},
        "data": {"dataset": "tokens", "augment": False, "device_layout": "gather"},
    }
