"""Sequence-recommendation template — next-item prediction over histories.

Long-context, first-class: the DataSource assembles each user's **full
time-ordered event stream** (view/buy/rate events sorted by eventTime —
the reference's nearest concept is Spark partitioning of the event RDD
along time; SURVEY.md §5 "long-context: ABSENT") and the algorithm trains
the causal transformer of pio_tpu/models/seqrec.py, whose training step
shards dp × sp (ring attention) × tp × ep × pp over the mesh.

engine.json:

    {
      "id": "seqrec",
      "engineFactory": "templates.sequence",
      "datasource": {"params": {"app_name": "myapp"}},
      "algorithms": [{"name": "seqrec", "params":
          {"d_model": 64, "n_layers": 2, "max_len": 64,
           "seq_parallel": 1, "pipe_parallel": 1}}]
    }

Query ``{"user": "u1", "num": 4}`` (or ``{"history": ["i1", "i2"], ...}``)
→ ``{"itemScores": [{"item": "i5", "score": 3.1}, ...]}``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from pio_tpu.controller import (
    Algorithm,
    DataSource,
    Engine,
    FirstServing,
    Params,
    Preparator,
    SanityCheck,
    register_engine,
)
from pio_tpu.data.bimap import BiMap
from pio_tpu.models.als import top_n
from pio_tpu.models.seqrec import SeqRecConfig, SeqRecModel, train_seqrec
from pio_tpu.parallel.context import ComputeContext
from pio_tpu.parallel.mesh import MeshSpec, build_mesh
from pio_tpu.storage import Storage
from pio_tpu.templates.common import (
    ItemScore,
    PredictedResult,
    fold_assignments,
    resolve_app,
)
from pio_tpu.workflow.shard_store import ShardableModel


# --------------------------------------------------------------- data source
@dataclasses.dataclass(frozen=True)
class DataSourceParams(Params):
    app_name: str = ""
    app_id: int = 0
    channel: str = ""
    #: events whose target entity enters the user's history, in time order
    event_names: Tuple[str, ...] = ("view", "buy", "rate")
    min_history: int = 2
    eval_k: int = 0  # >0 enables k-fold leave-last-out read_eval
    eval_num: int = 10


@dataclasses.dataclass
class TrainingData(SanityCheck):
    #: per user: time-ordered item-id history
    histories: Dict[str, List[str]]

    def sanity_check(self) -> None:
        if not self.histories:
            raise ValueError(
                "TrainingData is empty - no user event streams found. "
                "Did you import events for this app?"
            )

    def __len__(self):
        return len(self.histories)


class SequenceDataSource(DataSource):
    """Full event streams per user, ordered by eventTime."""

    params_class = DataSourceParams

    def read_training(self, ctx: ComputeContext) -> TrainingData:
        p: DataSourceParams = self.params
        app_id, channel_id = resolve_app(p)
        frame = Storage.get_pevents().find_frame(
            app_id,
            channel_id=channel_id,
            event_names=list(p.event_names),
            entity_type="user",
            target_entity_type="item",
        )
        order = np.argsort(frame.event_time_us, kind="stable")
        histories: Dict[str, List[str]] = {}
        for i in order:
            histories.setdefault(str(frame.entity_id[i]), []).append(
                str(frame.target_entity_id[i])
            )
        histories = {
            u: h for u, h in histories.items() if len(h) >= p.min_history
        }
        return TrainingData(histories=histories)

    def read_eval(self, ctx: ComputeContext):
        """k-fold leave-last-out next-item protocol: users split into k
        folds; a fold's users train on their history MINUS the last item
        and are queried with that prefix, the actual being the held-out
        last item (HitRate@eval_num ≡ next-item accuracy when
        eval_num=1). Other folds' users train on their full history."""
        p: DataSourceParams = self.params
        if p.eval_k <= 0:
            return []
        if p.eval_k == 1:
            raise ValueError("k-fold cross-validation needs eval_k >= 2")
        td = self.read_training(ctx)
        users = sorted(td.histories)
        # randomized (seeded) user folds: sorted user ids often encode
        # signup order, so sequential r % k would correlate folds with
        # user cohorts (see common.fold_assignments)
        fold_of = fold_assignments(len(users), p.eval_k)
        folds = []
        for k in range(p.eval_k):
            train_h: Dict[str, List[str]] = {}
            qa = []
            for r, u in enumerate(users):
                h = td.histories[u]
                if fold_of[r] == k and len(h) > p.min_history:
                    train_h[u] = h[:-1]
                    qa.append(
                        (Query(history=tuple(h[:-1]), num=p.eval_num),
                         str(h[-1]))
                    )
                else:
                    train_h[u] = h
            folds.append((TrainingData(histories=train_h), {"fold": k}, qa))
        return folds


# --------------------------------------------------------------- preparator
@dataclasses.dataclass
class PreparedData:
    item_index: BiMap  # code 0 is reserved for padding
    sequences: np.ndarray  # [n_users, T] int32, right-padded with 0
    user_rows: Dict[str, int]  # user id → row in sequences


class SequencePreparator(Preparator):
    """Index items (code 0 = pad) and pack histories into a dense matrix."""

    def prepare(self, ctx: ComputeContext, td: TrainingData) -> PreparedData:
        all_items: List[str] = []
        for h in td.histories.values():
            all_items.extend(h)
        # BiMap codes start at 0; shift by +1 so 0 stays the pad id.
        # Popularity ordering clusters hot embedding rows (the
        # vocab-sharded gather's locality) — codes stay deterministic.
        item_index = BiMap.string_int_by_frequency(all_items)
        fwd = item_index.to_dict()
        users = sorted(td.histories)
        t = max(len(td.histories[u]) for u in users)
        seqs = np.zeros((len(users), t), np.int32)
        for r, u in enumerate(users):
            h = td.histories[u]
            seqs[r, : len(h)] = [fwd[i] + 1 for i in h]
        return PreparedData(
            item_index=item_index,
            sequences=seqs,
            user_rows={u: r for r, u in enumerate(users)},
        )


# ----------------------------------------------------------------- algorithm
@dataclasses.dataclass(frozen=True)
class Query:
    user: str = ""
    history: Tuple[str, ...] = ()  # anonymous/session queries
    num: int = 10


@dataclasses.dataclass(frozen=True)
class SeqRecParams(SeqRecConfig, Params):
    """engine.json's algorithm params: every field of
    :class:`~pio_tpu.models.seqrec.SeqRecConfig` (the block is described
    there, by data: ``attention_kind``, ``ffn_kind``, ``dense_layers``,
    the expert and MTP counts; for ``"gqa"`` the ``layer_pattern`` of full
    and window layers, the heads held by kind, each kind's RoPE and the
    ``router_kind``; for layers that are one mixer alone the
    ``mixer_pattern`` of ``"mamba"``, ``"moe"``, ``"attn"`` and ``"mlp"``
    layers (``"mlp"``: the dense SwiGLU of width ``ffn`` as a layer of its
    own; the pattern may hold no ``"moe"`` at all), the
    ``ssm_*`` sizes of a Mamba-2 layer, ``expert_act``, ``attn_rope``,
    ``attn_gate`` and ``attn_qk_norm``; for ``"sparse"`` layers the
    indexer's ``index_heads``, ``index_head_dim`` and ``index_topk``; for
    any moe block ``expert_matmul``, the routed experts'
    grouped matmul, the model's four scalars ``embed_scale``,
    ``residual_scale``, ``attn_scale`` and ``logit_scale`` (each applied
    only where set) and ``tied_head``, which reads the logits from the
    embedding table) plus the mesh splits."""

    steps: int = 300
    #: mesh splits; remaining devices ride the data axis
    seq_parallel: int = 1
    pipe_parallel: int = 1
    model_parallel: int = 1


@dataclasses.dataclass
class SeqRecEngineModel(ShardableModel):
    model: SeqRecModel
    item_index: BiMap
    #: training-time histories for user-id queries
    user_histories: Dict[str, List[int]]

    shard_template = "seqrec"

    def shard_arrays(self):
        # flatten the layer-stacked params pytree with the same "/"
        # paths the partition rules match against
        out = {}
        for k, v in self.model.params.items():
            if isinstance(v, dict):
                for k2, v2 in v.items():
                    out[f"{k}/{k2}"] = v2
            else:
                out[k] = v
        return out

    def replace_shard_arrays(self, arrays):
        params: Dict = {}
        for name, arr in arrays.items():
            if "/" in name:
                outer, inner = name.split("/", 1)
                params.setdefault(outer, {})[inner] = arr
            else:
                params[name] = arr
        return dataclasses.replace(
            self, model=dataclasses.replace(self.model, params=params)
        )


class SeqRecAlgorithm(Algorithm):
    """Causal-transformer next-item training over the packed histories."""

    params_class = SeqRecParams
    query_class = Query

    def _mesh(self, ctx: ComputeContext):
        p: SeqRecParams = self.params
        if ctx.mesh is None:
            return None
        devices = list(ctx.mesh.devices.flat)
        n = len(devices)
        sp = max(1, min(p.seq_parallel, n))
        pp = max(1, min(p.pipe_parallel, n // sp))
        mp = max(1, min(p.model_parallel, n // (sp * pp)))
        return build_mesh(
            MeshSpec(data=-1, seq=sp, pipe=pp, model=mp), devices=devices
        )

    def train(
        self, ctx: ComputeContext, pd: PreparedData
    ) -> SeqRecEngineModel:
        p: SeqRecParams = self.params
        mesh = self._mesh(ctx)
        # train_seqrec keeps each row's NEWEST max_len events (tail), the
        # same window predict scores
        model = train_seqrec(
            mesh,
            pd.sequences,
            n_items=len(pd.item_index),
            config=SeqRecConfig(**{
                f.name: getattr(p, f.name)
                for f in dataclasses.fields(SeqRecConfig)
            }),
            checkpoint=ctx.checkpoint,
            checkpoint_every=ctx.checkpoint_every,
        )
        user_histories = {
            u: [int(x) for x in pd.sequences[r] if x > 0]
            for u, r in pd.user_rows.items()
        }
        return SeqRecEngineModel(model, pd.item_index, user_histories)

    def _history_codes(
        self, model: SeqRecEngineModel, query: Query
    ) -> Optional[List[int]]:
        if query.history:
            # O(1) lookups — to_dict() would copy the whole index per query
            codes = [
                c + 1
                for c in (
                    model.item_index.get(i) for i in query.history
                )
                if c is not None
            ]
            return codes or None
        return model.user_histories.get(query.user)

    def warmup_query(self, model: SeqRecEngineModel) -> Optional[Query]:
        """Any user with a training-time history drives the [B, T]
        transformer forward — enough to compile each serving bucket."""
        for u, hist in model.user_histories.items():
            if hist:
                return Query(user=u)
        return None

    def predict(
        self, model: SeqRecEngineModel, query: Query
    ) -> PredictedResult:
        codes = self._history_codes(model, query)
        if not codes:
            return PredictedResult()  # unknown user / empty history
        scores = model.model.next_item_scores(
            _history_rows([codes], model.model.config.max_len)
        )[0]
        return _seq_top_result(scores, query.num, model.item_index)

    def batch_predict(self, model: SeqRecEngineModel, queries):
        """Vectorized offline scoring: the transformer forward already
        takes a [B, T] batch — stack every resolvable history and run
        ONE device call instead of B."""
        out = []
        bidx, bq, bcodes = [], [], []
        for i, q in queries:
            codes = self._history_codes(model, q)
            if not codes:
                out.append((i, PredictedResult()))
                continue
            bidx.append(i)
            bq.append(q)
            bcodes.append(codes)
        if bidx:
            rows = _history_rows(bcodes, model.model.config.max_len)
            scores = model.model.next_item_scores(rows)
            for i, q, row in zip(bidx, bq, scores):
                out.append(
                    (i, _seq_top_result(row, q.num, model.item_index))
                )
        return out


def _history_rows(code_lists, max_len: int) -> np.ndarray:
    """Right-truncated, zero-padded [B, max_len] history batch."""
    rows = np.zeros((len(code_lists), max_len), np.int32)
    for r, codes in enumerate(code_lists):
        tail = codes[-max_len:]
        rows[r, : len(tail)] = tail
    return rows


def _seq_top_result(scores, num: int, item_index) -> PredictedResult:
    """Shared top-N tail (scores[0] is the pad row, shifted off here) so
    predict and batch_predict cannot diverge."""
    idx, vals = top_n(scores[1:], num)
    inv = item_index.inverse
    return PredictedResult(
        tuple(ItemScore(inv[int(i)], float(v)) for i, v in zip(idx, vals))
    )


class SequenceServing(FirstServing):
    pass


@register_engine("templates.sequence")
def sequence_engine() -> Engine:
    return Engine(
        SequenceDataSource,
        SequencePreparator,
        {"seqrec": SeqRecAlgorithm},
        SequenceServing,
    )


# -------------------------------------------------------------- evaluation
def sequence_evaluation(
    app_name: str = "",
    eval_k: int = 3,
    eval_num: int = 10,
    layer_grid=(1, 2),
    steps: int = 200,
    d_model: int = 32,
    max_len: int = 32,
):
    """Ready-made `pio eval` sweep: k-fold leave-last-out
    HitRate@``eval_num`` (next-item accuracy at eval_num=1) over a
    transformer-depth grid.

    Zero-arg CLI use reads the app from ``$PIO_TPU_EVAL_APP``:

        PIO_TPU_EVAL_APP=myapp python -m pio_tpu eval \\
            pio_tpu.templates.sequence:sequence_evaluation
    """
    from pio_tpu.controller.engine import EngineParams
    from pio_tpu.controller.evaluation import (
        EngineParamsGenerator, Evaluation,
    )
    from pio_tpu.templates.common import eval_app_name
    from pio_tpu.templates.similarproduct import HitRateMetric

    if eval_k < 2:
        raise ValueError("k-fold evaluation needs eval_k >= 2")
    ds = DataSourceParams(
        app_name=eval_app_name(app_name), eval_k=eval_k, eval_num=eval_num
    )
    grid = [
        EngineParams(
            data_source_params=ds,
            algorithm_params_list=(
                ("seqrec", SeqRecParams(
                    d_model=d_model, n_layers=n, steps=steps,
                    max_len=max_len,
                )),
            ),
        )
        for n in layer_grid
    ]
    return Evaluation(
        sequence_engine(), HitRateMetric(),
        engine_params_generator=EngineParamsGenerator(grid),
    )
