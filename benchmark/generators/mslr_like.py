"""MSLR-WEB30K-shaped synthetic ranking rows (the reference's MS LTR
experiment: 137 dense numeric columns of query-document features, graded
relevance 0-4, queries of about 120 documents), made on the device in blocks
of whole queries.

The source's columns are what a web search engine computes for a query and a
document, over the body, anchor, title, URL and whole document: how many of
the query's terms each stream covers, stream lengths, term frequencies, TF-IDF
and BM25-style scores, language-model scores, ratios normalised by length,
link counts, page and site ranks, click counts and dwell time. Here they are
three kinds, each driven by one latent relevance of the document, by eight
topic factors every column loads on, and by noise of its own:

* columns 0..45 (``COUNTS``, 46): small whole counts with many zeros, 30% to
  70% zeros a column (covered terms, term frequencies, stream lengths, links,
  clicks);
* columns 46..106 (``SCORES``, 61): positive heavy-tailed scores, log-normal
  (IDF, TF-IDF and BM25 sums, language-model scores, ranks, dwell time);
* columns 107..136 (``RATIOS``, 30): ratios in [0, 1], 5% to 40% exactly 0 a
  column (covered-term ratios, length-normalised term frequencies).

A query is ``GROUP`` = 120 documents, the source's mean (its lengths run from
1 to over 1,000; see the configuration's ``assumed``). A document's grade is
its relevance plus a shared offset of its query plus judging noise, cut at the
population's quantiles so that the grades 0, 1, 2, 3, 4 take about
``GRADE_SHARES`` of the rows, near the source's. The loadings, zero shares,
scales and cuts are the population's, from a fixed key, the same under every
seed: a seed draws the documents and queries. They are made up, not fitted to
MSLR-WEB30K.

The test fold is the population's too. The configuration trains on blocks
0..31 and holds out queries of block ``TEST_FOLD`` = 32, and a block from
there on is drawn from a fixed key (``HELDOUT``), the same under every seed,
as MSLR-WEB30K's test fold is one set of judged queries: a seed moves the
training queries and the model, not the queries the model is scored on.
NDCG@10 of one query spreads by about 0.2 between queries, so over 1,000
queries drawn anew a seed the held-out score would move by about 0.8% (one
standard deviation) for the held-out draw alone.

One jitted function of (key, block index): f32 from the start, and any block
can be made again later, bit for bit, by calling the same compiled function,
which is how the reference gets the rows without the program's copy.
"""
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri


def _refuse_a_program_whose_ranking_fill_drops_its_lambdas():
    """A program from before the ranking fill was mended sends the lane
    numbers of its rows through a float32 scatter, bitcast to float32: an
    integer under 2^23 read as a float32 is a denormal, which XLA flushes to
    zero inside the fused program, so every lambda of a lane under 8,388,608
    lands on lane 0 and those rows train on a gradient of 0 (trees of one
    leaf, after minutes of set-up at this cell's size). A small probe of the
    fill run on its own does not show it (it came back clean on the chip,
    PERF.md section 7 row 0c), so look at what the fill computes: trace the
    program's own ranking fill at a tiny shape and refuse, with run.py's own
    exit code for a cell it cannot run, where a bitcast takes the lane
    numbers (an iota) to a float."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective
    n, lanes = 10, 16
    meta = types.SimpleNamespace(
        label=np.asarray([0, 1, 2, 0, 4, 3, 0, 1, 0, 2], np.float64),
        weight=None, num_queries=2, query_boundaries=np.asarray([0, 5, 10]))
    obj = create_objective("lambdarank", Config({"objective": "lambdarank"}))
    obj.init(meta, n)
    _, fn = obj.device_gradients()
    jaxpr = jax.make_jaxpr(fn)(
        jnp.zeros((lanes,), jnp.float32), jnp.arange(lanes, dtype=jnp.int32),
        jnp.arange(lanes) < n, *obj.persist_grad_args()).jaxpr
    lane_ids = [e.outvars[0] for e in jaxpr.eqns
                if e.primitive.name == "iota"]
    for e in jaxpr.eqns:
        if (e.primitive.name == "bitcast_convert_type"
                and any(e.invars[0] is v for v in lane_ids)
                and jnp.issubdtype(e.outvars[0].aval.dtype, jnp.floating)):
            sys.stderr.write(
                "benchmark: configuration mslr needs a program whose ranking "
                "fill keeps its lane numbers integers (lightgbm_tpu/"
                "objectives/rank.py:payload_pos_fn bitcasts them to %s); "
                "this one cannot run it\n" % e.outvars[0].aval.dtype)
            raise SystemExit(2)


_refuse_a_program_whose_ranking_fill_drops_its_lambdas()

FEATURES = 137
GROUP = 120          # documents a query; a block is a whole number of them
COUNTS, SCORES, RATIOS = 46, 61, 30
FACTORS = 8          # topic factors every column loads on
TABLES = 30000       # key of the population's tables (MSLR-WEB30K)
HELDOUT = 30001      # key of the population's test fold
TEST_FOLD = 32       # first block of the test fold: 11,520,000 / 360,000
QUERY = 0.5          # weight of the query's shared offset in a grade
NOISE = 0.6          # weight of the judging noise in a grade
GRADE_SHARES = (0.52, 0.32, 0.13, 0.02, 0.01)
SUB = 12000          # rows made at a time (100 queries)


def tables():
    """The population's fixed tables: per column its loading on relevance
    [F], on the topic factors [FACTORS, F], its own noise weight [F], the
    (mu, sd) of its scale [F] and the cut under which it reads 0 [F]; and
    the four grade cuts [4]."""
    kr, kw, km, kmu, ksd, kz = jax.random.split(jax.random.key(TABLES), 6)
    # a third of the columns carry most of the relevance, the rest a little
    strong = jax.random.uniform(kr, (FEATURES,)) < 0.35
    w = jnp.where(strong, jax.random.uniform(kw, (FEATURES,), minval=0.3,
                                             maxval=0.75),
                  jax.random.uniform(kw, (FEATURES,), maxval=0.15))
    mix = 0.45 * jax.random.normal(km, (FACTORS, FEATURES), jnp.float32)
    own = jnp.sqrt(jnp.maximum(1.0 - w * w - jnp.sum(mix * mix, axis=0),
                               0.1))
    kind = jnp.arange(FEATURES)
    is_count = kind < COUNTS
    is_ratio = kind >= COUNTS + SCORES
    mu = jnp.where(is_count, jax.random.uniform(kmu, (FEATURES,), maxval=3.0),
                   jax.random.uniform(kmu, (FEATURES,), minval=-1.0,
                                      maxval=3.0))
    sd = jax.random.uniform(ksd, (FEATURES,), minval=0.5, maxval=1.4)
    zero = jnp.where(is_count,
                     jax.random.uniform(kz, (FEATURES,), minval=0.3,
                                        maxval=0.7),
                     jnp.where(is_ratio,
                               jax.random.uniform(kz, (FEATURES,),
                                                  minval=0.05, maxval=0.4),
                               0.0))
    cut0 = jnp.where(zero > 0, ndtri(jnp.maximum(zero, 1e-6)), -jnp.inf)
    spread = np.sqrt(1.0 + QUERY ** 2 + NOISE ** 2)
    grade_cuts = spread * ndtri(jnp.asarray(np.cumsum(GRADE_SHARES)[:4],
                                            jnp.float32))
    return w, mix, own, mu, sd, cut0, grade_cuts


def _make_rows(key, rows):
    """(X [rows, 137] f32, grade [rows] f32) from ``key``; ``rows`` is a
    whole number of queries."""
    w, mix, own, mu, sd, cut0, grade_cuts = tables()
    kz, kq, ke, kf, kn = jax.random.split(key, 5)
    z = jax.random.normal(kz, (rows,), jnp.float32)            # relevance
    q = jnp.repeat(jax.random.normal(kq, (rows // GROUP,), jnp.float32),
                   GROUP)
    f = jax.random.normal(kf, (rows, FACTORS), jnp.float32)
    a = (z[:, None] * w + f @ mix
         + jax.random.normal(kn, (rows, FEATURES), jnp.float32) * own)
    a = a / jnp.sqrt(w * w + jnp.sum(mix * mix, axis=0) + own * own)
    scale = jnp.exp(mu + sd * a)
    kind = jnp.arange(FEATURES)
    x = jnp.where(kind < COUNTS, jnp.floor(scale),
                  jnp.where(kind < COUNTS + SCORES, scale,
                            jax.nn.sigmoid(1.7 * a)))
    x = jnp.where(a < cut0, 0.0, x)
    r = z + QUERY * q + NOISE * jax.random.normal(ke, (rows,), jnp.float32)
    grade = jnp.sum(r[:, None] > grade_cuts, axis=1).astype(jnp.float32)
    return x.astype(jnp.float32), grade


def make_block(key, index, rows):
    """(X [rows, 137] f32, grade [rows] f32) of block ``index``; ``rows`` is
    a whole number of queries; from ``TEST_FOLD`` on, the same under every
    ``key``. Made ``SUB`` rows at a time where ``rows``
    holds a whole number of them: the compiler keeps some forty [rows, 137]
    temporaries of the random draws alive, 16 GB at 360,000 rows at once."""
    fold = jax.random.key_data(jax.random.key(HELDOUT))
    key = jax.random.wrap_key_data(
        jnp.where(index >= TEST_FOLD, fold, jax.random.key_data(key)))
    key = jax.random.fold_in(key, index)
    if rows % SUB:
        return _make_rows(key, rows)
    x, grade = jax.lax.map(
        lambda i: _make_rows(jax.random.fold_in(key, i), SUB),
        jnp.arange(rows // SUB))
    return x.reshape(rows, FEATURES), grade.reshape(rows)
