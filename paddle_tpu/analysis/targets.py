"""Lint targets: every program the repo ships, built for analysis.

One place (shared by ``python -m paddle_tpu.analysis`` and the tier-1
gate test tests/test_analysis_gate.py) that knows how to BUILD each
models/ and benchmark/ program so the checker suite can lint it. Model
builds use small dims — the IR structure (op types, sub-blocks,
companions, param naming) is what the checkers read, and it is
invariant to width — so the whole zoo builds in well under a minute on
CPU. Benchmark programs go through benchmark/fluid_benchmark.py's own
adapters (its default arg shapes) so the exact programs the harness
times are the programs that get linted.

Each target yields ``LintTarget(name, programs, pairs)`` where
`programs` maps a label -> Program (main + startup builds) and `pairs`
lists (label_a, label_b) program pairs for the pairwise sweep named by
``pair_check``: "shared_params" (the default — builds that SHARE
weights by name through one scope, check_shared_params/PTA051) or
"cross_model" (co-resident but UNRELATED serving-runtime models,
check_cross_model_collision/PTA100, where any name overlap is the
defect). Targets that build DecodeStepBundles also carry them in
``bundles`` (label -> bundle) so the whole-bundle contract sweep
(checkers.check_bundle / PTA150) lints each bundle AS A UNIT — the
per-program sweep cannot see cross-specialization disagreements.
"""
from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, List, Tuple

__all__ = ["LintTarget", "iter_lint_targets", "MODEL_BUILDERS"]


@dataclass
class LintTarget:
    name: str
    programs: Dict[str, object]              # label -> Program
    pairs: List[Tuple[str, str]] = field(default_factory=list)
    pair_check: str = "shared_params"        # or "cross_model"
    bundles: Dict[str, object] = field(default_factory=dict)


def _mnist():
    from ..models import mnist

    main, startup, *_ = mnist.build_program(use_conv=True)
    return {"main": main, "startup": startup}, []


def _resnet():
    from ..models import resnet

    main, startup, _ = resnet.build_program(
        depth=50, class_dim=10, image_shape=(3, 32, 32))
    return {"main": main, "startup": startup}, []


def _vgg():
    from ..models import vgg

    main, startup, _ = vgg.build_program(class_dim=10,
                                         image_shape=(3, 32, 32))
    return {"main": main, "startup": startup}, []


def _se_resnext():
    from ..models import se_resnext

    main, startup, _ = se_resnext.build_program(
        class_dim=10, image_shape=(3, 64, 64))
    return {"main": main, "startup": startup}, []


def _stacked_dynamic_lstm():
    from ..models import stacked_dynamic_lstm

    main, startup, *_ = stacked_dynamic_lstm.build_program(
        dict_dim=1000, emb_dim=64, hid_dim=64, stacked_num=2)
    return {"main": main, "startup": startup}, []


def _machine_translation():
    from ..models import machine_translation as mt

    kw = dict(src_dict_dim=1000, tgt_dict_dim=1000, embedding_dim=32,
              encoder_size=32, decoder_size=32)
    main, startup, _ = mt.build_program(**kw)
    dec = mt.build_decode_program(src_len=8, max_len=8, **kw)
    return ({"main": main, "startup": startup, "decode": dec[0],
             "decode_startup": dec[1]},
            [("main", "decode")])


def _transformer():
    from ..models import transformer as tr
    from ..models.decode_engine import (CacheConfig, DraftConfig,
                                        SamplingConfig)

    kw = dict(seq_len=16, d_model=64, n_heads=4, n_layers=2,
              d_inner=128, vocab=1000)
    main, startup, _ = tr.build_program(dropout_rate=0.1, **kw)
    dkw = dict(seq_len=8, max_out_len=8, d_model=64, n_heads=4,
               n_layers=2, d_inner=128, vocab=1000)
    greedy = tr.build_greedy_decode_program(**dkw)
    incr = tr.build_incremental_decode_program(**dkw)
    beam = tr.build_beam_decode_program(**dkw)
    bundle = tr.build_decode_step_program(n_slots=4, **dkw)
    big = max(bundle.prefills)
    # paged decode-engine layout (block pool + prefix entries): the
    # PTA110 shared-pool sweep and the rest of the suite cover every
    # program flavor the paged server dispatches
    paged = tr.build_decode_step_program(
        n_slots=4, state_prefix="@cbp/",
        cache=CacheConfig(layout="paged", block_size=4, n_blocks=8,
                          n_prompt_entries=3), **dkw)
    pbig = max(paged.prefills)
    # speculative draft-and-verify (r14): the draft prefill/propose,
    # target verify and fused serve programs join the strict zoo —
    # dense AND paged (PTA110 covers the multi-position verify
    # scatter, PTA120 the advance bound), plus a sampled-lane step
    draft = DraftConfig(d_model=32, n_heads=2, n_layers=1,
                        d_inner=64, k=2, k_options=(0, 2))
    # ONE admission bucket per spec-flavor bundle: program structure
    # is bucket-invariant, and the spec serve programs are the
    # biggest builds in the zoo — the gate must stay fast (tier-1).
    # The k-ladder (r19) adds the ("k", 0, base) adaptive variants —
    # the draft-keepalive + plain-body composition — to the sweep.
    spec = tr.build_decode_step_program(
        n_slots=4, state_prefix="@cbs/", draft=draft,
        admit_buckets=[2], **dkw)
    sbig = max(spec.prefills)
    # model-free drafting (r19): the n-gram/prompt-copy propose body
    # (shift-matrix suffix matcher, one-hot dprobs) + its adaptive
    # k=0 rung join the strict zoo
    ngram = tr.build_decode_step_program(
        n_slots=4, state_prefix="@cbn/", admit_buckets=[2],
        draft=DraftConfig(k=2, kind="ngram", ngram=2,
                          k_options=(0, 2)), **dkw)
    pspec = tr.build_decode_step_program(
        n_slots=4, state_prefix="@cbps/", draft=draft,
        admit_buckets=[2],
        cache=CacheConfig(layout="paged", block_size=4, n_blocks=8,
                          n_prompt_entries=3), **dkw)
    psbig = max(pspec.prefills)
    sampled = tr.build_decode_step_program(
        n_slots=4, state_prefix="@cbt/", admit_buckets=[2],
        sampling=SamplingConfig(temperature=0.8, top_k=8,
                                top_p=0.95), **dkw)
    # chunked prefill (ISSUE 17): the ("chunked", p) phase programs
    # join the strict zoo — the embed scatter (0), a kv staging
    # phase (1), an attention phase (2) and the cross-KV install
    # (2L+1) cover every distinct phase-body shape; the bundle
    # contract sweep (PTA150) checks the full set
    chunked = tr.build_decode_step_program(
        n_slots=4, state_prefix="@cbc/", admit_buckets=[2],
        cache=CacheConfig(layout="paged", block_size=4, n_blocks=8,
                          n_prompt_entries=3, chunk_tokens=4), **dkw)
    ckph = len(chunked.chunk_phase_keys) - 1
    # deliberately-misconfigured capacity wedge (PTA200): 5 distinct
    # never-closing session prompts against 3 pinnable prompt entries
    # is the session-pinning admission deadlock the protomodel proves
    # (protomodel.session_protocol) — the zoo keeps it as a COUNTED
    # suppressed witness so the checker's positive case is regression-
    # gated without turning the strict gate red
    wedge = copy.copy(paged)
    wedge.workload = {"distinct_session_prompts": 5,
                      "sessions_close": False}
    wedge._pta_suppress = (
        ("PTA200", "deliberate witness: session-pinning deadlock "
                   "(5 pinned prompts > 3 entries) kept as the "
                   "PTA200 regression wedge"),)
    return ({"main": main, "startup": startup, "greedy": greedy[0],
             "incremental": incr[0], "beam": beam[0],
             "cb_prefill": bundle.prefill,
             f"cb_prefill{big}": bundle.prefills[big],
             "cb_step": bundle.step,
             "cb_serve0": bundle.serves[0],
             f"cb_serve{big}": bundle.serves[big],
             "pg_prefill": paged.prefill,
             f"pg_hit_prefill{pbig}": paged.hit_prefills[pbig],
             "pg_step": paged.step,
             "pg_serve0": paged.serves[0],
             f"pg_serve_miss{pbig}": paged.serves[("miss", pbig)],
             f"pg_serve_hit{pbig}": paged.serves[("hit", pbig)],
             f"pg_serve_radix{pbig}": paged.serves[("radix", pbig)],
             "pg_cow": paged.cow,
             "pg_probe": paged.probe,
             "sp_prefill": spec.prefill,
             "sp_step": spec.step,
             "sp_serve0": spec.serves[0],
             f"sp_serve{sbig}": spec.serves[sbig],
             f"sp_serve_k0_{sbig}": spec.serves[("k", 0, sbig)],
             "ng_step": ngram.step,
             f"ng_serve{sbig}": ngram.serves[sbig],
             f"ng_serve_k0_{sbig}": ngram.serves[("k", 0, sbig)],
             "sps_step": pspec.step,
             f"sps_serve_miss{psbig}": pspec.serves[("miss", psbig)],
             f"sps_serve_hit{psbig}": pspec.serves[("hit", psbig)],
             "smp_step": sampled.step,
             "smp_serve0": sampled.serves[0],
             "ck_chunk_embed": chunked.serves[("chunked", 0)],
             "ck_chunk_kv": chunked.serves[("chunked", 1)],
             "ck_chunk_attn": chunked.serves[("chunked", 2)],
             f"ck_chunk_cross{ckph}":
                 chunked.serves[("chunked", ckph)]},
            [("main", "greedy"), ("main", "incremental"),
             ("main", "beam"), ("main", "cb_prefill"),
             ("main", f"cb_prefill{big}"), ("main", "cb_step"),
             ("main", "cb_serve0"), ("main", f"cb_serve{big}"),
             ("main", "pg_prefill"), ("main", "pg_step"),
             ("main", f"pg_serve_miss{pbig}"),
             ("main", f"pg_serve_hit{pbig}"),
             ("main", f"pg_serve_radix{pbig}"),
             ("main", "pg_cow"), ("main", "pg_probe"),
             ("main", "sp_step"), ("main", f"sp_serve{sbig}"),
             ("main", f"sp_serve_k0_{sbig}"),
             ("main", "ng_step"), ("main", f"ng_serve_k0_{sbig}"),
             ("main", f"sps_serve_miss{psbig}"),
             ("main", "smp_step"),
             ("main", "ck_chunk_kv"),
             ("main", f"ck_chunk_cross{ckph}")],
            "shared_params",
            # whole-bundle contract sweep (PTA150): every bundle the
            # repo ships, checked as a unit
            {"cb": bundle, "pg": paged, "sp": spec, "sps": pspec,
             "ng": ngram, "smp": sampled, "ck": chunked,
             "pg_wedge": wedge})


def _moe_transformer():
    from ..models import moe_transformer

    main, startup, _ = moe_transformer.build_program(
        seq_len=16, vocab=1000, d_model=64, n_heads=4, n_layers=2,
        d_inner=128, n_experts=4)
    return {"main": main, "startup": startup}, []


def _lfm2_moe():
    from ..models import lfm2_moe

    main, startup, _ = lfm2_moe.build_program(
        seq_len=16, vocab=256, d_model=64, n_heads=4, n_kv_heads=2,
        n_layers=5, n_dense_layers=1, d_dense=128, d_expert=64,
        n_experts=8, top_k=2, experts_held=(2, 4))
    return {"main": main, "startup": startup}, []


def _ctr():
    from ..models import ctr

    main, startup, *_ = ctr.build_program(dnn_dict_dim=1001,
                                          lr_dict_dim=1001)
    return {"main": main, "startup": startup}, []


def _word2vec():
    from ..models import word2vec

    main, startup, *_ = word2vec.build_program(dict_size=500,
                                               embed_size=16,
                                               hidden_size=32)
    return {"main": main, "startup": startup}, []


def _recommender():
    from ..models import recommender

    main, startup, *_ = recommender.build_program()
    return {"main": main, "startup": startup}, []


def _label_semantic_roles():
    from ..models import label_semantic_roles

    main, startup, *_ = label_semantic_roles.build_program(seq_len=8)
    return {"main": main, "startup": startup}, []


def _sharded_decoder():
    """The tp-sharded decode engine — the REAL sharded serving
    lowerings (models/decode_engine.ShardingConfig), linted as zoo
    targets: the dense fixture bundle's step + serve programs AND a
    paged+speculative tp bundle, so PTA130/131/160/161 prove every
    shipped sharded serve While branch-free of misplaced collectives
    and PTA190/191 keep the sharded pools' ownership proofs. The
    baseline's ``sharding_facts`` section snapshots the propagated
    specs of all of them."""
    from .. import unique_name
    from ..models import sharded_decoder
    from ..models import transformer as tr
    from ..models.decode_engine import (CacheConfig, DraftConfig,
                                        ShardingConfig)

    fx = sharded_decoder.build_tp_sharded_decoder_step()
    b = fx.bundle
    big = max(b.prefills)
    with unique_name.guard():
        # paged + speculative tp bundle: the sharded pools under the
        # ownership prover + the (k+1)-query verify under the
        # sharding prover, in one build (ONE admission bucket — the
        # gate must stay fast, the targets.py spec-bundle discipline)
        ps = tr.build_decode_step_program(
            seq_len=8, max_out_len=8, d_model=32, n_heads=4,
            n_layers=1, d_inner=64, vocab=64, n_slots=4,
            state_prefix="@tpps/", admit_buckets=[2],
            draft=DraftConfig(d_model=16, n_heads=2, n_layers=1,
                              d_inner=32, k=2),
            cache=CacheConfig(layout="paged", block_size=4,
                              n_blocks=8, n_prompt_entries=3),
            sharding=ShardingConfig(tp=2, qkv_interleaved=True))
    pbig = max(ps.prefills)
    return ({"step": fx.program, "startup": fx.startup,
             "serve0": b.serves[0], f"serve{big}": b.serves[big],
             "prefill": b.prefill,
             "ps_step": ps.step,
             "ps_serve0": ps.serves[0],
             f"ps_serve_miss{pbig}": ps.serves[("miss", pbig)],
             f"ps_serve_hit{pbig}": ps.serves[("hit", pbig)],
             f"ps_prefill{pbig}": ps.prefills[pbig]},
            [("step", "serve0"), ("step", f"serve{big}"),
             ("ps_step", f"ps_serve_miss{pbig}")],
            "shared_params",
            {"tp": b, "tpps": ps})


def _serving_runtime():
    """The multi-tenant runtime's model zoo (inference/runtime/zoo.py
    — the exact programs bench.py's `multitenant` config serves).
    Every distinct model pair is also lint-PAIRED so PTA051/PTA100's
    shared-name sweeps cover the co-residency contract (distinct
    per-model prefixes must keep them silent)."""
    from ..inference.runtime import zoo

    programs = {}
    names = []
    for prefix, in_dim, hidden, classes in zoo.DEFAULT_ZOO:
        main, startup, _feeds, _fetches = zoo.build_fc_program(
            prefix, in_dim, hidden, classes)
        programs[prefix] = main
        programs[f"{prefix}_startup"] = startup
        names.append(prefix)
    pairs = [(a, b) for i, a in enumerate(names)
             for b in names[i + 1:]]
    return programs, pairs, "cross_model"


MODEL_BUILDERS: Dict[str, Callable] = {
    "mnist": _mnist,
    "resnet": _resnet,
    "vgg": _vgg,
    "se_resnext": _se_resnext,
    "stacked_dynamic_lstm": _stacked_dynamic_lstm,
    "machine_translation": _machine_translation,
    "transformer": _transformer,
    "moe_transformer": _moe_transformer,
    "lfm2_moe": _lfm2_moe,
    "ctr": _ctr,
    "word2vec": _word2vec,
    "recommender": _recommender,
    "label_semantic_roles": _label_semantic_roles,
    "serving_runtime": _serving_runtime,
    "sharded_decoder": _sharded_decoder,
}


def match_targets(only: Optional[List[str]]) -> List[str]:
    """Model names selected by the --only SUBSTRING filters (a lint
    iteration loop types `--only transformer`, not the full target
    name): every model whose ``models/<name>`` contains any filter.
    Empty/None selects everything."""
    if not only:
        return list(MODEL_BUILDERS)
    return [name for name in MODEL_BUILDERS
            if any(s in f"models/{name}" for s in only)]


def _benchmark_targets() -> Iterator[LintTarget]:
    """The benchmark harness's own program builds (its default arg
    shapes). Importable only with the repo root on sys.path; callers
    treat ImportError as 'no benchmark package here'."""
    from benchmark.fluid_benchmark import MODELS, parse_args

    for name, adapter in sorted(MODELS.items()):
        args = parse_args(["--model", name, "--batch_size", "4"])
        main, startup, _loss, _feed, _unit = adapter(args)
        yield LintTarget(f"benchmark/{name}",
                         {"main": main, "startup": startup})


def iter_lint_targets(include_benchmark: bool = True,
                      only: List[str] = None) -> Iterator[LintTarget]:
    selected = set(match_targets(only))
    for name, build in MODEL_BUILDERS.items():
        if only and name not in selected:
            continue
        built = build()
        programs, pairs = built[0], built[1]
        pair_check = built[2] if len(built) > 2 else "shared_params"
        bundles = built[3] if len(built) > 3 else {}
        yield LintTarget(f"models/{name}", programs, pairs,
                         pair_check=pair_check, bundles=bundles)
    if include_benchmark and not only:
        try:
            yield from _benchmark_targets()
        except ImportError:
            pass
