#!/usr/bin/env python
"""Train DALL-E (stage 2) on paired text+image data — TPU-native CLI.

Capability parity with the reference trainer (`/root/reference/train_dalle.py`):
same flag surface (``--vae_path | --dalle_path`` mutually exclusive,
``--image_text_folder``, ``--truncate_captions``,
``--random_resize_crop_lower_ratio``, ``--chinese``, ``--taming``,
``--bpe_path``, ``--fp16``, ``--learning_rate`` + distributed flags; ref
:29-61), same CUB-200 hyperparameters (ref :74-97), same checkpoint payload
``{'hparams', 'vae_params', 'weights'}`` with the reference's cadence
(``dalle.pt`` every 100 iters, ``./sweep1/{run}-{epoch}.pt`` every 19th
epoch, ``dalle-final.pt`` at the end; ref :174-184, :405, :425-426, :431),
same plain-text log (one ``epoch iter loss lr`` line per step into
``{run}.txt``; ref :351-353, :378), ReduceLROnPlateau on the epoch loss
(ref :286-295, :415-416) and a sample generation every 100 iters
(ref :396-412).

TPU-native redesign: the frozen VAE tokenizes images *inside* the jitted
train step (stop-gradient), GSPMD data parallelism replaces
DeepSpeed/Horovod, ``--fp16`` selects bf16 compute (the TPU-native mixed
precision — no loss scaling needed), and resume checkpoints additionally
carry optimizer + scheduler state (fixing the gap noted in SURVEY.md §5.3).
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from dalle_pytorch_tpu import DALLE, DALLEConfig, DiscreteVAE, VAEConfig
from dalle_pytorch_tpu.cli import host_fetch, select_tokenizer, enable_compilation_cache
from dalle_pytorch_tpu.data.dataset import DataLoader, TextImageDataset
from dalle_pytorch_tpu.models.dalle import generate_codes
from dalle_pytorch_tpu.obs import mem as obs_mem
from dalle_pytorch_tpu.obs import prof
from dalle_pytorch_tpu.obs import telemetry as obs
from dalle_pytorch_tpu.parallel import backend as distributed_utils
from dalle_pytorch_tpu.training import (make_dalle_train_step, make_optimizer,
                                        set_learning_rate)
from dalle_pytorch_tpu.utils import faults, guardrails
from dalle_pytorch_tpu.utils.checkpoint import load_checkpoint, save_checkpoint
from dalle_pytorch_tpu.utils.ckpt_manager import (CheckpointManager,
                                                  config_fingerprint)
from dalle_pytorch_tpu.utils.failure import GracefulShutdown, Heartbeat
from dalle_pytorch_tpu.utils.images import save_image
from dalle_pytorch_tpu.utils.logging import TrainLogger
from dalle_pytorch_tpu.utils.schedule import ReduceLROnPlateau


def exists(val):
    return val is not None


def parse_args(argv=None):
    parser = argparse.ArgumentParser()
    group = parser.add_mutually_exclusive_group(required=False)
    group.add_argument('--vae_path', type=str,
                       help='path to your trained discrete VAE')
    group.add_argument('--dalle_path', type=str,
                       help='path to your partially trained DALL-E')
    parser.add_argument('--image_text_folder', type=str, required=True,
                        help='path to your folder of images and text for '
                             'learning the DALL-E (with --data_format '
                             'shards: the shard directory holding '
                             'index.json + shard-*.tar, see '
                             'tools/make_shards.py)')
    parser.add_argument('--data_format', choices=('folder', 'shards'),
                        default='folder',
                        help="input pipeline: 'folder' lists loose files "
                             "(the reference layout); 'shards' streams tar "
                             "shards with per-host shard assignment and a "
                             "fingerprinted resume cursor — same batches, "
                             "bitwise, under the same seed")
    parser.add_argument('--truncate_captions', action='store_true',
                        help='Captions passed in which exceed the max token '
                             'length will be truncated if this is set.')
    parser.add_argument('--random_resize_crop_lower_ratio', dest='resize_ratio',
                        type=float, default=0.6,
                        help='Random resized crop lower ratio')
    parser.add_argument('--chinese', dest='chinese', action='store_true')
    parser.add_argument('--taming', dest='taming', action='store_true')
    parser.add_argument('--bpe_path', type=str,
                        help='path to your BPE file: a huggingface tokenizer '
                             'json or a CLIP merges txt')
    parser.add_argument('--fp16', action='store_true',
                        help='mixed precision (bf16 on TPU — no loss scaling '
                             'needed, unlike the reference\'s fp16)')
    parser.add_argument('--learning_rate', default=3e-4)
    parser.add_argument('--epochs', type=int, default=5,
                        help='training epochs (the reference hard-codes '
                             'EPOCHS=5 but its committed logs ran 100)')
    parser.add_argument('--profile_dir', type=str, default=None,
                        help='write a jax.profiler trace of steps 10-20 of '
                             'the first epoch to this dir (XProf/TensorBoard)')
    parser.add_argument('--xprof_dir', type=str, default=None,
                        help='managed on-chip trace window (obs/prof.py '
                             'capture: the trace rides a prof.xprof '
                             'telemetry span); GRAFT_XPROF env arms it '
                             'without a flag, GRAFT_XPROF_WINDOW=a:b moves '
                             'the step window. Alias of --profile_dir')
    parser.add_argument('--heartbeat_dir', type=str, default=None,
                        help='write per-process heartbeat-p{i}.json progress '
                             'files here for external stall/death monitors')
    parser.add_argument('--telemetry_dir', type=str, default=None,
                        help='graftscope run telemetry: append a schema-'
                             'versioned events.jsonl (per-step records, '
                             'ckpt/health/fault/serve events, spans) here '
                             'for tools/obs_report.py; GRAFT_TELEMETRY=0 '
                             'hard-disables even when set')
    parser.add_argument('--metrics_port', type=int, default=0,
                        help='serve /metrics (Prometheus text) + /healthz '
                             'from an in-process daemon thread on this '
                             'port (+ process index, so multi-host runs '
                             'on one box do not collide); series are fed '
                             'by the telemetry emit path. 0 disables')
    parser.add_argument('--alerts', action=argparse.BooleanOptionalAction,
                        default=True,
                        help='attach the declarative alert engine (obs/'
                             'alerts.py DEFAULT_RULES: stall fraction, '
                             'MFU drop vs run median, quarantine rate, '
                             'heartbeat gap) to the telemetry stream — '
                             'fired alerts are emitted as `alert` events '
                             'causally after their cause and printed to '
                             'stderr. No-op without --telemetry_dir')
    parser.add_argument('--stall_timeout', type=float, default=0,
                        help='warn on stderr when no step completes for this '
                             'many seconds (0 disables the in-process '
                             'watchdog); requires --heartbeat_dir')
    parser.add_argument('--health', choices=('off', 'warn', 'skip',
                                             'rollback'), default='skip',
                        help='training-health guardrails: every step '
                             'computes an on-device health vector (loss, '
                             'grad norm, finite flag). warn: observe only; '
                             'skip (default): additionally mask the update '
                             'when grads are non-finite so params/optimizer '
                             'are never poisoned; rollback: additionally '
                             'roll back to the newest valid managed '
                             'checkpoint on loss spikes / divergence, '
                             'skipping the offending data window with an '
                             'LR backoff, bounded by --max_rollbacks')
    parser.add_argument('--step_deadline', type=float, default=0,
                        help='hung-step watchdog: if a training step takes '
                             'longer than this many seconds (compile-bearing '
                             'first step exempt), dump all thread stacks and '
                             'exit with the documented wedge code (75) so a '
                             'supervisor relaunches with --resume auto. '
                             '0 disables')
    parser.add_argument('--max_rollbacks', type=int, default=3,
                        help='anomaly-recovery budget for --health '
                             'rollback; exhausting it aborts with exit '
                             'code 70 (rollback-budget-exhausted)')
    parser.add_argument('--spike_zscore', type=float, default=8.0,
                        help='robust z-score (|loss-median| / 1.4826*MAD '
                             'over a rolling window) above which a finite '
                             'loss counts as a spike')
    parser.add_argument('--sharded_checkpoints', action='store_true',
                        help='save Orbax sharded checkpoint dirs '
                             '({name}.orbax) with per-host shard IO instead '
                             'of gathering to process 0 (for multi-host '
                             'scale); load sites accept both formats')
    parser.add_argument('--resume', type=str, default=None,
                        help="'auto': resume from the newest manifest-valid "
                             'checkpoint in --ckpt_dir, skipping torn or '
                             'corrupt ones; any other value is an explicit '
                             'checkpoint path (same as --dalle_path). '
                             'Resumes are exact mid-epoch: data order, RNG '
                             'stream, optimizer, and scheduler continue '
                             'bitwise from the interrupted step')
    parser.add_argument('--ckpt_dir', type=str, default='./checkpoints',
                        help='managed checkpoint run dir: one '
                             'ckpt-{step:08d}/ per save, each with an '
                             'integrity manifest (per-file crc32) published '
                             'by atomic rename only after the data lands')
    parser.add_argument('--keep_checkpoints', type=int, default=3,
                        help='retention: keep the newest N managed '
                             'checkpoints (0 keeps all)')
    parser.add_argument('--keep_every', type=int, default=0,
                        help='retention: additionally keep every managed '
                             'checkpoint whose step is a multiple of M')
    parser.add_argument('--ckpt_every', type=int, default=100,
                        help='managed-checkpoint cadence in steps (0 '
                             'disables the CheckpointManager entirely)')
    parser.add_argument('--ckpt_async', action=argparse.BooleanOptionalAction,
                        default=True,
                        help='write managed checkpoints from a background '
                             'thread (device arrays still snapshot to host '
                             'synchronously; the atomic manifest publish '
                             'stays the sole commit point, so the '
                             'crash-consistency invariants are unchanged). '
                             '--no-ckpt_async restores blocking saves; '
                             'Orbax sharded saves are always blocking '
                             '(collective)')
    parser.add_argument('--mesh_sp', type=int, default=1,
                        help='sequence-parallel ways: shard the sequence '
                             'over an sp mesh axis with exact ring/Ulysses '
                             'attention (long-context training; seq_len must '
                             'divide by this)')
    parser.add_argument('--sp_impl', choices=('ring', 'ulysses'),
                        default='ring',
                        help='sequence-parallel scheme: ring (k/v rotation) '
                             'or ulysses (head<->sequence all-to-all; needs '
                             'heads %% mesh_sp == 0)')
    parser.add_argument('--pipeline_stages', type=int, default=1,
                        help='pipeline-parallel stages (GPipe schedule): '
                             'depth must divide by this and each stage must '
                             'hold whole attn-type cycles. Checkpoints are '
                             'saved weights-only in this mode (optimizer '
                             'moments are stage-stacked)')
    parser.add_argument('--pipeline_microbatches', type=int, default=4,
                        help='GPipe microbatches per step (batch_size must '
                             'divide by this)')
    parser.add_argument('--ff_experts', type=int, default=0,
                        help='>1: replace feed-forwards with top-k routed '
                             'MoE layers of this many experts (a model '
                             'hyperparameter — stored in checkpoints)')
    parser.add_argument('--ff_expert_top_k', type=int, default=2,
                        help='experts routed per token when --ff_experts > 1')
    parser.add_argument('--ff_expert_dispatch', choices=('dense', 'capacity'),
                        default='dense',
                        help="MoE dispatch: 'dense' (every expert sees every "
                             "token, exact) or 'capacity' (GShard-style "
                             "fixed slots; FLOPs scale with top_k x "
                             "capacity factor instead of expert count)")
    parser.add_argument('--ff_expert_capacity_factor', type=float,
                        default=1.25,
                        help="slot headroom for 'capacity' dispatch")
    parser.add_argument('--trunk', type=str, default=None,
                        help="train DALL-E's client over a named trunk "
                             "(dalle_pytorch_tpu/presets.py, e.g. jamba2-3b, "
                             "jamba-tiny): dim, depth, heads and the "
                             "per-layer block spec come from the preset; "
                             "the text vocabulary, the text length and the "
                             "image geometry stay this run's")
    parser = distributed_utils.wrap_arg_parser(parser)
    args = parser.parse_args(argv)
    # resolve the declarative ParallelPlan (--plan wins over the individual
    # mesh flags and writes the resolved axis sizes back onto args) BEFORE
    # the flag validation below, so a plan-driven sp/pp run validates the
    # same way a flag-driven one does
    from dalle_pytorch_tpu.parallel.plan import resolve_plan_args
    try:
        args.run_plan = resolve_plan_args(args)
    except ValueError as e:
        parser.error(str(e))
    if args.stall_timeout and not args.heartbeat_dir:
        parser.error('--stall_timeout requires --heartbeat_dir')
    if args.resume and args.dalle_path:
        parser.error('--resume and --dalle_path are mutually exclusive '
                     '(--resume auto resolves the checkpoint itself)')
    if args.mesh_sp > 1 and args.pipeline_stages > 1:
        parser.error('--mesh_sp and --pipeline_stages are mutually exclusive')
    if (args.mesh_sp > 1 or args.pipeline_stages > 1) and (
            args.mesh_fsdp > 1 or args.mesh_tp > 1 or args.mesh_dcn_dp > 1):
        parser.error('--mesh_sp/--pipeline_stages own the non-dp mesh axis; '
                     'combine with --mesh_fsdp/--mesh_tp/--mesh_dcn_dp is '
                     'not supported')
    if args.ff_experts > 1 and args.mesh_sp > 1:
        parser.error('--ff_experts with --mesh_sp is not supported')
    if args.ff_experts > 1 and args.pipeline_stages > 1:
        parser.error('--ff_experts with --pipeline_stages is not supported')
    return args


def build_vae(args, distr_backend, resume_vae_params=None):
    """VAE reconstitution priority (ref train_dalle.py:116-165):
    resume hparams > custom --vae_path > pretrained (OpenAI dVAE / taming
    VQGAN via --taming).  Returns (vae, vae_hparams_or_None, weights_or_None);
    `vae` is either a DiscreteVAE flax module or a duck-typed pretrained
    wrapper exposing image_size/num_layers/num_tokens +
    get_codebook_indices/decode (ref dalle_pytorch.py:308-313)."""
    if resume_vae_params is not None:
        cfg = VAEConfig.from_dict(resume_vae_params)
        return DiscreteVAE(cfg), cfg, cfg.to_dict(), None

    if exists(args.vae_path):
        if distr_backend.is_root_worker():
            print(f'using pretrained VAE {args.vae_path} for encoding images')
        ckpt = load_checkpoint(args.vae_path)
        cfg = VAEConfig.from_dict(dict(ckpt['hparams']))
        return DiscreteVAE(cfg), cfg, cfg.to_dict(), ckpt['weights']

    # pretrained path: requires converted weights on disk (no egress here)
    from dalle_pytorch_tpu.models.pretrained_vae import (OpenAIDiscreteVAE,
                                                         VQGanVAE1024)
    if distr_backend.is_root_worker():
        print('using pretrained VAE for encoding images')
    wrapper = VQGanVAE1024() if args.taming else OpenAIDiscreteVAE()
    # the reference stores vae_params=None for pretrained VAEs and rebuilds
    # them from the --taming flag on load (ref train_dalle.py:167-172)
    return wrapper, wrapper, None, wrapper.params


def main(argv=None):
    """CLI entry: the real run (`_main`) inside the rollback-and-skip
    escalation loop — a `RollbackAndSkip` escape from the anomaly policy
    relaunches with `--resume auto`, the offending data window skipped and
    the LR backed off, bounded by --max_rollbacks (then exit code 70)."""
    argv = list(sys.argv[1:] if argv is None else argv)
    return guardrails.run_with_rollback(_main, argv)


def _main(argv, lr_scale=1.0, skip_past=None):
    enable_compilation_cache()
    args = parse_args(argv)

    # constants (ref train_dalle.py:74-97); sweep/test overrides via
    # $DALLE_TPU_HPARAMS (JSON), replacing the reference's edit-the-file
    # sweep workflow (SURVEY.md §5.6)
    C = dict(
        BATCH_SIZE=16,
        GRAD_CLIP_NORM=0,
        MODEL_DIM=256,
        TEXT_SEQ_LEN=80,
        DEPTH=8,
        HEADS=8,
        DIM_HEAD=64,
        REVERSIBLE=False,
        LOSS_IMG_WEIGHT=7,
        ATTN_TYPES=('full', 'axial_row', 'axial_col', 'conv_like'),
        LR_DECAY_FACTOR=0.5,
        LR_DECAY_PATIENCE=5,
        LR_DECAY_COOLDOWN=0,
        LR_DECAY_MIN=1e-7,
        TRUNK=None,
    )
    if args.trunk:
        from dalle_pytorch_tpu.presets import preset_config
        named = preset_config(args.trunk)
        assert named.trunk is not None, (
            f"--trunk {args.trunk}: that preset is the DALL-E block itself")
        C.update(MODEL_DIM=named.dim, DEPTH=named.depth, HEADS=named.heads,
                 DIM_HEAD=named.dim_head,
                 ATTN_TYPES=named.attn_types or ('full',),
                 TRUNK=named.to_dict()['trunk'])
    import json as _json
    import os as _os
    # graftlint: disable=ENV001 (JSON-valued: presence of any override dict is the signal)
    if _os.environ.get('DALLE_TPU_HPARAMS'):
        C.update(_json.loads(_os.environ['DALLE_TPU_HPARAMS']))

    EPOCHS = args.epochs
    BATCH_SIZE = C['BATCH_SIZE']
    LEARNING_RATE = float(args.learning_rate)
    GRAD_CLIP_NORM = C['GRAD_CLIP_NORM']

    MODEL_DIM = C['MODEL_DIM']
    TEXT_SEQ_LEN = C['TEXT_SEQ_LEN']
    DEPTH = C['DEPTH']
    HEADS = C['HEADS']
    DIM_HEAD = C['DIM_HEAD']
    REVERSIBLE = C['REVERSIBLE']
    LOSS_IMG_WEIGHT = C['LOSS_IMG_WEIGHT']
    ATTN_TYPES = tuple(C['ATTN_TYPES'])

    LR_DECAY_FACTOR = C['LR_DECAY_FACTOR']
    LR_DECAY_PATIENCE = C['LR_DECAY_PATIENCE']
    LR_DECAY_COOLDOWN = C['LR_DECAY_COOLDOWN']
    LR_DECAY_MIN = C['LR_DECAY_MIN']

    distr_backend = distributed_utils.set_backend_from_args(args)
    distr_backend.initialize()
    distr_backend.check_batch_size(BATCH_SIZE)

    # chaos rehearsal hooks (GRAFT_FAULTS) — re-parsed per run so in-process
    # reruns (tests) see the current environment, not a cached spec
    faults.install_from_env()

    # crash-consistent managed checkpoints: one manifest-validated dir per
    # save under --ckpt_dir, with retention + auto-resume fallback.  Every
    # manifest records the writing plan + topology (elastic resume
    # provenance): a relaunch under a different --plan or device count
    # reshards the restore and says so below.
    from dalle_pytorch_tpu.parallel.plan import (current_topology,
                                                 describe_transition)
    manager = (CheckpointManager(args.ckpt_dir,
                                 keep_last=args.keep_checkpoints,
                                 keep_every=args.keep_every,
                                 sharded=args.sharded_checkpoints,
                                 async_save=args.ckpt_async,
                                 plan=args.run_plan.to_manifest(),
                                 topology=current_topology())
               if args.ckpt_every > 0 else None)
    if args.resume == 'auto':
        info = manager.latest_valid() if manager is not None else None
        if info is not None:
            args.dalle_path = str(info.payload)
            if distr_backend.is_root_worker():
                print(f'auto-resume: step {info.step} from {info.payload}')
                transition = describe_transition(
                    info.manifest.get('plan'), args.run_plan,
                    info.manifest.get('topology'))
                if transition:
                    print(f'[resume] {transition}')
        elif distr_backend.is_root_worker():
            print(f'auto-resume: no valid checkpoint under {args.ckpt_dir}; '
                  'starting fresh')
    elif args.resume:
        args.dalle_path = args.resume

    # execution-plan config overrides (NOT stored in checkpoints): the model
    # function is identical to dense, only the collectives differ
    sp_plan = {}
    if args.mesh_sp > 1:
        sp_plan = dict(ring_axis='sp', sp_impl=args.sp_impl,
                       sp_size=args.mesh_sp)
    # MoE dispatch is also per-run execution strategy over the same params:
    # CLI-selectable on fresh runs AND resumes (not stored in checkpoints)
    sp_plan.update(ff_expert_dispatch=args.ff_expert_dispatch,
                   ff_expert_capacity_factor=args.ff_expert_capacity_factor)
    # (tp meshes keep the phase-sliced head: PhaseLogits stores one kernel
    # per vocab phase, each tp-sharded on its own vocab dim, so the phase
    # boundary is a param boundary — no interior-slice resharding)
    pp_mode = args.pipeline_stages > 1

    # training-health guardrails (utils/guardrails.py): health vector on
    # device, update masked on non-finite grads, host-side anomaly policy
    health_on = args.health != 'off'
    health_guard = args.health in ('skip', 'rollback')

    tokenizer = select_tokenizer(args.bpe_path, chinese=args.chinese)
    dtype = jnp.bfloat16 if args.fp16 else jnp.float32

    # model reconstitution: resume or fresh (ref :116-165)
    resume_ckpt = None
    resume_sharded = None  # Orbax dir: arrays restore direct-to-device later
    start_epoch = 0
    start_step = 0
    resume_rng = None
    resume_loader = None
    resume_epoch_losses: list = []
    if exists(args.dalle_path):
        from dalle_pytorch_tpu.utils.checkpoint import (is_sharded_checkpoint,
                                                        load_sharded_small)

        dalle_path = Path(args.dalle_path)
        assert dalle_path.exists(), 'DALL-E model file does not exist'
        if is_sharded_checkpoint(dalle_path):
            # two-phase elastic resume: configs/scalars now; arrays restore
            # straight onto this run's shardings after the mesh exists — no
            # host materialization, works across topology changes
            resume_sharded = dalle_path
            resume_ckpt = load_sharded_small(dalle_path)
        else:
            resume_ckpt = load_checkpoint(dalle_path)
            # normalize to host numpy so the standard shard_params /
            # opt-template flow below re-places everything
            resume_ckpt = jax.tree.map(
                lambda v: np.asarray(v) if hasattr(v, 'devices') else v,
                resume_ckpt)
        resume_vae = resume_ckpt.get('vae_params')
        vae, vae_geom, vae_hparams, vae_weights = build_vae(
            args, distr_backend,
            resume_vae_params=dict(resume_vae) if resume_vae else None)
        if (vae_weights is None and resume_sharded is None
                and resume_ckpt.get('vae_weights') is not None):
            vae_weights = resume_ckpt['vae_weights']
        dalle_cfg = DALLEConfig.from_dict(dict(resume_ckpt['hparams']),
                                          dtype=dtype, **sp_plan)
        # the checkpoint's geometry wins over the script constants — a resume
        # of a non-default run must rebuild the exact model (ref :116-133)
        TEXT_SEQ_LEN = dalle_cfg.text_seq_len
        start_epoch = int(resume_ckpt.get('epoch', 0))
        # exact-resume extras (all plain scalars, so both the msgpack and
        # the two-phase sharded restore deliver them here)
        start_step = int(resume_ckpt.get('global_step', 0))
        resume_rng = resume_ckpt.get('rng')
        resume_loader = resume_ckpt.get('loader')
        resume_epoch_losses = [float(v) for v in
                               (resume_ckpt.get('epoch_losses') or [])]
    else:
        vae, vae_geom, vae_hparams, vae_weights = build_vae(args, distr_backend)
        dalle_cfg = DALLEConfig.from_vae(
            vae_geom,
            dim=MODEL_DIM,
            num_text_tokens=tokenizer.vocab_size,
            text_seq_len=TEXT_SEQ_LEN,
            depth=DEPTH,
            heads=HEADS,
            dim_head=DIM_HEAD,
            reversible=REVERSIBLE,
            loss_img_weight=LOSS_IMG_WEIGHT,
            attn_types=ATTN_TYPES,
            ff_experts=args.ff_experts,
            ff_expert_top_k=args.ff_expert_top_k,
            trunk=C['TRUNK'],
            dtype=dtype,
            **sp_plan,
        )
    dalle = DALLE(dalle_cfg)
    if manager is not None:
        # saves record the config identity; latest_valid refuses checkpoints
        # of a *different* model on later resumes
        manager.fingerprint = config_fingerprint(dalle_cfg.to_dict())
    # dense twin: identical param tree, no sp collectives — used for init
    # (which runs the forward outside any shard_map) and for sampling
    import dataclasses as _dc
    dalle_dense = (DALLE(_dc.replace(dalle_cfg, ring_axis=None, sp_size=1))
                   if sp_plan else dalle)

    if args.data_format == 'shards':
        # streaming ingestion: tar shards + index manifest, per-host shard
        # assignment, the same iteration contract (data/stream.py)
        from dalle_pytorch_tpu.data.stream import (ShardStreamDataset,
                                                   StreamingDataLoader)

        ds = ShardStreamDataset(
            args.image_text_folder, tokenizer, text_len=TEXT_SEQ_LEN,
            image_size=vae_geom.image_size, resize_ratio=args.resize_ratio,
            truncate_captions=args.truncate_captions,
        )
        dl = StreamingDataLoader(
            ds, BATCH_SIZE, shuffle=True, drop_last=True,
            shard_num_hosts=jax.process_count(),
            shard_index=jax.process_index(),
        )
    else:
        ds = TextImageDataset(
            args.image_text_folder, tokenizer, text_len=TEXT_SEQ_LEN,
            image_size=vae_geom.image_size, resize_ratio=args.resize_ratio,
            truncate_captions=args.truncate_captions,
        )
        dl = DataLoader(
            ds, BATCH_SIZE, shuffle=True, drop_last=True,
            shard_num_hosts=jax.process_count(),
            shard_index=jax.process_index(),
        )
    assert len(ds) > 0, 'dataset is empty'
    if distr_backend.is_root_worker():
        print(f'{len(ds)} image-text pairs found for training')
    # exact mid-epoch resume: replay the interrupted epoch's permutation and
    # skip the batches already consumed.  A loader snapshot from an earlier
    # epoch (final/sweep checkpoints, written after the epoch-end step) just
    # aligns the permutation stream and starts the epoch fresh.  The loaders
    # coerce their own scalar types (the streaming cursor also carries the
    # shard-list fingerprint, a string, which it validates itself).
    resume_cursor = 0
    if resume_loader is not None and \
            int(dict(resume_loader).get('epoch', -1)) == start_epoch:
        dl.load_state_dict(dict(resume_loader))
        resume_cursor = min(int(dict(resume_loader).get('cursor', 0)),
                            len(dl))
    else:
        dl.epoch = start_epoch
        resume_epoch_losses = []

    rng = jax.random.PRNGKey(42)
    rng, init_rng = jax.random.split(rng)
    dummy_text = jnp.zeros((1, TEXT_SEQ_LEN), jnp.int32)
    dummy_codes = jnp.zeros((1, dalle_cfg.image_seq_len), jnp.int32)
    # ONE construction path for every plan (dp/fsdp/tp/dcn AND sp/pp): the
    # resolved ParallelPlan builds the mesh and the Partitioner, and init /
    # restore / the step-output pin all derive from that partitioner
    part = distr_backend.distribute(plan=args.run_plan)
    if resume_sharded is not None:
        # no device allocation at all: phase 2 below restores straight onto
        # ShapeDtypeStruct templates, so an elastic resume never holds a
        # discarded random init alongside the restored arrays (that 2x peak
        # would bite exactly when resuming onto less hardware)
        param_shapes = jax.eval_shape(
            lambda r: dalle_dense.init(r, dummy_text, dummy_codes)['params'],
            init_rng)
        params = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            param_shapes, part.param_shardings(param_shapes))
    else:
        params = jax.jit(
            lambda r: dalle_dense.init(r, dummy_text, dummy_codes)['params']
        )(init_rng)
        if resume_ckpt is not None:
            from dalle_pytorch_tpu.utils.checkpoint import (
                migrate_head_kernels, migrate_qkv_kernels)

            params = jax.tree.map(
                jnp.asarray,
                migrate_head_kernels(
                    migrate_qkv_kernels(resume_ckpt['weights'],
                                        dim_head=dalle_cfg.dim_head),
                    dalle_cfg.total_text_tokens))
        params = part.shard_params(params)
    is_custom_vae = isinstance(vae, DiscreteVAE)
    if vae_weights is not None:
        vae_params = part.replicate(jax.tree.map(jnp.asarray, vae_weights))
    elif is_custom_vae and resume_sharded is not None:
        # shapes only — the real weights restore in phase 2 below; eval_shape
        # avoids a compile + device compute and, unlike the random-init
        # branch, consumes no rng split (keeping the post-resume RNG stream
        # identical between sharded and msgpack checkpoints of the same run)
        dummy_img = jnp.zeros((1, vae_geom.image_size, vae_geom.image_size, 3))
        vae_shapes = jax.eval_shape(
            lambda r: vae.init({'params': r, 'gumbel': r}, dummy_img)['params'],
            jax.random.PRNGKey(0))
        vae_params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=part.repl_sharding),
            vae_shapes)
    elif is_custom_vae:
        # fresh random VAE only makes sense in smoke tests; a real run always
        # has weights, matching the reference's hard requirement of a VAE.
        rng, vae_rng = jax.random.split(rng)
        dummy_img = jnp.zeros((1, vae_geom.image_size, vae_geom.image_size, 3))
        vae_params = part.replicate(jax.jit(
            lambda r: vae.init({'params': r, 'gumbel': r}, dummy_img)['params']
        )(vae_rng))
    else:
        vae._require_params()  # pretrained wrapper without converted weights
        vae_params = None

    tx = make_optimizer(LEARNING_RATE, grad_clip_norm=GRAD_CLIP_NORM)

    train_step_pp = None
    if pp_mode:
        assert resume_sharded is None, (
            '--pipeline_stages resumes from msgpack checkpoints only (the '
            'sharded two-phase restore targets the dense layout)')
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dalle_pytorch_tpu.training import make_dalle_pp_train_step

        # restructure params {'outer', 'stages'} and place each stage's
        # slice on its pipeline device (leading-axis 'pp' sharding)
        train_step_pp, params = make_dalle_pp_train_step(
            dalle, tx, params, part.mesh,
            num_microbatches=args.pipeline_microbatches,
            health=health_on, guard=health_guard)
        _stage_shard = NamedSharding(part.mesh, P('pp'))  # graftlint: disable=PLAN001 (pp stacks stage params on a leading stage dim sharded by POSITION over 'pp' — a structural axis the path-regex rule table cannot name)

        def _pp_shard(path, leaf):
            in_stages = any(getattr(k, 'key', None) == 'stages' for k in path)
            return (_stage_shard if in_stages and getattr(leaf, 'ndim', 0) > 0
                    else part.repl_sharding)

        params = jax.device_put(
            params, jax.tree_util.tree_map_with_path(_pp_shard, params))

    if resume_sharded is not None:
        # abstract init: params are ShapeDtypeStructs here, and the real
        # moments arrive from the checkpoint in phase 2 — allocating zeros
        # first would only raise the restore's peak memory
        opt_state = jax.eval_shape(tx.init, params)
    elif pp_mode:
        # Adam moments follow the stage-stacked layout
        opt_sds = jax.eval_shape(tx.init, params)
        opt_state = jax.jit(tx.init, out_shardings=jax.tree_util.
                            tree_map_with_path(_pp_shard, opt_sds))(params)
    else:
        opt_state = part.init_opt_state(tx, params)
    if resume_sharded is not None:
        # phase 2 of the elastic resume: swap each array placeholder for a
        # ShapeDtypeStruct carrying THIS run's sharding (params/opt/vae
        # templates above), then restore — every host reads only its shards,
        # directly onto the current mesh, whatever topology wrote the ckpt
        from dalle_pytorch_tpu.utils.checkpoint import (
            load_checkpoint_sharded, migrate_head_kernels)

        target = dict(resume_ckpt)
        target['weights'] = params  # already ShapeDtypeStructs w/ shardings
        # checkpoints written before the per-phase head split store a joint
        # to_logits_dense/{kernel,bias}: restore that pair replicated, then
        # split it onto this run's per-phase shardings after the restore
        legacy_head = 'kernel' in resume_ckpt.get('weights', {}).get(
            'to_logits_dense', {})
        if legacy_head:
            new_head_tmpl = params['to_logits_dense']  # keep: shardings
            target['weights'] = dict(params)
            # int() casts: restored hparams carry 0-d numpy scalars, which
            # sharding.shard_shape cannot hash inside a shape tuple
            target['weights']['to_logits_dense'] = {
                'kernel': jax.ShapeDtypeStruct(
                    (int(dalle_cfg.dim), int(dalle_cfg.total_tokens)),
                    jnp.float32, sharding=part.repl_sharding),
                'bias': jax.ShapeDtypeStruct(
                    (int(dalle_cfg.total_tokens),), jnp.float32,
                    sharding=part.repl_sharding)}
        restore_opt = 'opt_state' in resume_ckpt and not legacy_head
        if 'opt_state' in resume_ckpt and legacy_head:
            # the legacy moment lists no longer align leaf-for-leaf with the
            # split-head template (2 head leaves became 4): leave their
            # `...` placeholders in the target so orbax skips reading them,
            # and restart the optimizer rather than zip-truncate silently
            if distr_backend.is_root_worker():
                print('legacy joint-head checkpoint: weights migrated to the '
                      'per-phase head; optimizer state restarts fresh')
        elif restore_opt:
            target['opt_state'] = [
                sds if saved is ... else saved
                for sds, saved in zip(part.opt_state_templates(opt_state),
                                      resume_ckpt['opt_state'])]
        # ckpt VAE weights are used only when nothing else supplied them
        # (--vae_path wins, matching the msgpack path's precedence); when
        # skipped, their placeholders in `target` make the restore skip
        # reading them entirely
        vae_from_ckpt = ('vae_weights' in resume_ckpt and is_custom_vae
                         and any(isinstance(l, jax.ShapeDtypeStruct)
                                 for l in jax.tree.leaves(vae_params)))
        if vae_from_ckpt:
            target['vae_weights'] = vae_params  # ShapeDtypeStruct templates
        restored = load_checkpoint_sharded(resume_sharded, target=target)
        params = restored['weights']
        if legacy_head:
            head = migrate_head_kernels(
                {'to_logits_dense': {
                    k: np.asarray(v)
                    for k, v in params['to_logits_dense'].items()}},
                dalle_cfg.total_text_tokens)['to_logits_dense']
            params = dict(params)
            params['to_logits_dense'] = {
                k: jax.device_put(jnp.asarray(head[k]), tmpl.sharding)
                for k, tmpl in new_head_tmpl.items()}
        if restore_opt and 'opt_state' in restored:
            # big arrays restored onto their templates' shardings pass
            # through untouched; 0-d leaves (optax count) restored by value
            # get cast back to the template dtype
            fitted = [
                v if (hasattr(v, 'sharding') and getattr(v, 'ndim', 0) > 0)
                else (jax.device_put(jnp.asarray(v, tmpl.dtype),
                                     part.repl_sharding)
                      if hasattr(tmpl, 'dtype') else v)
                for tmpl, v in zip(jax.tree.leaves(opt_state),
                                   restored['opt_state'])]
            opt_state = jax.tree.unflatten(jax.tree.structure(opt_state),
                                           fitted)
        else:
            # weights-only checkpoint: fall back to fresh optimizer state
            opt_state = part.init_opt_state(tx, params)
        if vae_from_ckpt:
            vae_params = restored['vae_weights']
        elif is_custom_vae:
            assert not any(isinstance(l, jax.ShapeDtypeStruct)
                           for l in jax.tree.leaves(vae_params)), (
                f'{resume_sharded} carries no vae_weights but the run needs '
                'a custom VAE — pass --vae_path for its weights')
    elif resume_ckpt is not None and 'opt_state' in resume_ckpt and pp_mode:
        if distr_backend.is_root_worker():
            print('--pipeline_stages: checkpointed optimizer state targets '
                  'the dense layout; continuing with fresh optimizer state')
    elif resume_ckpt is not None and 'opt_state' in resume_ckpt:
        from dalle_pytorch_tpu.utils.checkpoint import migrate_head_kernels

        # legacy joint-head Adam moments split the same way the params do
        # (leaf COUNT changes, so this must happen before the unflatten)
        migrate_head_kernels(resume_ckpt['opt_state'],
                             dalle_cfg.total_text_tokens)

        def _fit_leaf(tmpl, v):
            if not hasattr(tmpl, 'dtype'):
                return v
            v = jnp.asarray(v)
            if v.shape != tmpl.shape and v.size == tmpl.size:
                # legacy flat fused-QKV adam moments -> DenseGeneral layout
                # (same migration migrate_qkv_kernels applies to the params)
                v = v.reshape(tmpl.shape)
            return v.astype(tmpl.dtype)

        opt_state = jax.tree.map(
            _fit_leaf,
            opt_state, jax.tree.unflatten(jax.tree.structure(opt_state),
                                          jax.tree.leaves(resume_ckpt['opt_state'])))

    if args.mesh_sp > 1 or pp_mode:
        # sp/pp steps consume codes: the VAE encodes outside their
        # shard_map'd loss (the codes feed is replicated/dp-sharded data)
        if args.mesh_sp > 1:
            from dalle_pytorch_tpu.training import make_dalle_sp_train_step

            _codes_step = make_dalle_sp_train_step(
                dalle, tx, part.mesh, health=health_on, guard=health_guard)
        else:
            _codes_step = train_step_pp
        if is_custom_vae:
            encode_fn = jax.jit(lambda vp, imgs: vae.apply(
                {'params': vp}, imgs,
                method=DiscreteVAE.get_codebook_indices))

            def train_step(params, opt_state, vae_params, text, images, rng,
                           *fs):
                # codes are concrete int32 outputs of a separate jit — no
                # gradient path into the frozen VAE exists to stop
                codes = encode_fn(vae_params, images)
                return _codes_step(params, opt_state, None, text, codes,
                                   rng, *fs)
        else:
            encode_fn = jax.jit(vae.get_codebook_indices)

            def train_step(params, opt_state, _vae_params, text, images, rng,
                           *fs):
                return _codes_step(params, opt_state, None, text,
                                   encode_fn(images), rng, *fs)
    elif is_custom_vae:
        # frozen DiscreteVAE tokenizes images inside the jitted step
        train_step = make_dalle_train_step(dalle, tx, vae=vae,
                                           health=health_on,
                                           guard=health_guard,
                                           partitioner=part)
    else:
        # pretrained wrapper: encode outside (its params are jit-captured
        # constants), feed codes into a codes-only step
        _codes_step = make_dalle_train_step(dalle, tx, vae=None,
                                            health=health_on,
                                            guard=health_guard,
                                            partitioner=part)
        encode_fn = jax.jit(vae.get_codebook_indices)

        def train_step(params, opt_state, _vae_params, text, images, rng,
                       *fs):
            codes = encode_fn(images)
            return _codes_step(params, opt_state, None, text, codes, rng, *fs)

    if resume_rng is not None:
        # the checkpointed RNG stream continues bitwise: every subsequent
        # step/generation split replays exactly as the uninterrupted run's
        rng = jnp.asarray(np.asarray([int(v) for v in resume_rng],
                                     dtype=np.uint32))

    # device-prefetch double buffer (both data formats): batch k+1 is
    # pulled, cast, and device-placed while step k runs, and the wrapper
    # meters what the step loop actually waited on the input pipeline
    # (loader_stall_s — ridden on heartbeats and the perf extras below).
    # Checkpoints MUST record batches.state_dict(), not dl.state_dict():
    # the loader's own cursor runs ahead by the prefetch depth, and a
    # resume from it would skip a never-trained batch.
    from dalle_pytorch_tpu.data.stream import DevicePrefetcher

    def _place_batch(batch):
        text, images = batch
        return part.shard_batch((text.astype(np.int32), images))

    batches = DevicePrefetcher(dl, place=_place_batch, depth=1)

    sched = ReduceLROnPlateau(
        LEARNING_RATE, factor=LR_DECAY_FACTOR, patience=LR_DECAY_PATIENCE,
        cooldown=LR_DECAY_COOLDOWN, min_lr=LR_DECAY_MIN)
    if resume_ckpt is not None and 'scheduler' in resume_ckpt:
        sched.load_state_dict({k: float(v) if isinstance(v, (int, float)) else v
                               for k, v in dict(resume_ckpt['scheduler']).items()})
    if lr_scale != 1.0:
        # rollback LR backoff: the restored checkpoint predates the
        # rollback, so the accumulated scale (0.5 per rollback) applies to
        # whatever lr the scheduler had at that point
        sched.lr = max(sched.lr * lr_scale, sched.min_lr)
        opt_state = set_learning_rate(opt_state, sched.lr)
        if distr_backend.is_root_worker():
            print(f'[guardrails] rollback lr backoff: lr={sched.lr:.3e}')

    logger = TrainLogger(
        project='dalle_tpu_train_transformer',
        config=dict(dalle_cfg.to_dict(), epochs=EPOCHS, batch_size=BATCH_SIZE,
                    learning_rate=LEARNING_RATE),
    )

    # graftscope run telemetry (obs/): one events.jsonl per run — every
    # layer below (ckpt manager, guardrails, faults, loader, serve) emits
    # into the installed singleton; disabled (a None get()) when no dir.
    # --metrics_port starts the /metrics + /healthz endpoint (fed by the
    # emit path) and --alerts attaches the declarative rule engine, so
    # fired alerts land in the SAME stream, causally after their cause.
    metrics_server = None
    if args.metrics_port:
        from dalle_pytorch_tpu.obs import metrics as obs_metrics
        metrics_server = obs_metrics.serve(
            args.metrics_port + jax.process_index())
    if args.telemetry_dir:
        tel = obs.init(args.telemetry_dir, run_id=logger.run_name,
                       host=jax.process_index())
        if metrics_server is not None:
            tel.attach_metrics(metrics_server.registry)
        if args.alerts:
            from dalle_pytorch_tpu.obs.alerts import AlertEngine
            tel.attach_alerts(AlertEngine())
        obs.emit('run', 'run_start', step=start_step, epoch=start_epoch,
                 config_fingerprint=config_fingerprint(dalle_cfg.to_dict()),
                 resumed_from=(str(args.dalle_path)
                               if exists(args.dalle_path) else None),
                 trainer='train_dalle')
        # predicted-vs-measured: announce the perf ledger's roofline
        # ceiling for this config (exact fingerprint first, plan-level
        # fallback).  obs_report joins it with StepTimer's measured MFU;
        # the mfu_vs_predicted alert rule reads it as its reference.
        import dataclasses as _dc
        _plan_name = args.run_plan.name
        _prof_target = ('dalle_pp' if pp_mode else
                        'dalle_sp' if sp_plan else 'dalle') + '/' + _plan_name
        _fp = prof.row_fingerprint({
            **{k: str(v) for k, v in
               sorted(_dc.asdict(dalle_cfg).items())},
            'target': _prof_target, 'plan': _plan_name,
            'batch': BATCH_SIZE * jax.process_count()})
        _pred = prof.predicted_for(fingerprint=_fp, target=_prof_target,
                                   plan=_plan_name)
        if _pred is not None:
            obs.emit('prof', 'predicted', target=_prof_target, **_pred)
        # the memory half of the same join (graftmem): the ledger's
        # predicted HBM timeline for this config, emitted once so
        # obs_report can set it beside the measured watermarks below
        _mempred = obs_mem.predicted_memory_for(
            fingerprint=_fp, target=_prof_target, plan=_plan_name)
        if _mempred is not None:
            obs.emit('mem', 'predicted', target=_prof_target, **_mempred)

    @jax.jit
    def decode_images(vae_params, codes):
        if is_custom_vae:
            return vae.apply({'params': vae_params}, codes,
                             method=DiscreteVAE.decode)
        return vae.decode(codes)

    def dense_params_view():
        """The standard DALLE param tree, whatever layout training uses —
        checkpoints and the sampler always see the dense structure."""
        if pp_mode:
            from dalle_pytorch_tpu.training import pp_params_to_dense

            return pp_params_to_dense(dalle, params, part.mesh)
        return params

    # the partial epoch's losses ride in checkpoints so the plateau
    # scheduler's epoch mean is bitwise identical after a mid-epoch resume;
    # one shared list object (cleared in place per epoch) so every save
    # closure sees the live values
    epoch_losses: list = list(resume_epoch_losses)

    def resume_extras():
        """Exact-resume state riding in every checkpoint payload: the RNG
        stream, the loader position (epoch/cursor/seed), the step counter,
        and the in-flight epoch's losses — all plain scalars, so both
        checkpoint formats restore them without device state."""
        extras = {
            'rng': [int(v) for v in np.asarray(jax.device_get(rng))],
            # the prefetcher's view: the cursor of the batch the step loop
            # actually holds, not the loader's read-ahead position
            'loader': batches.state_dict(),
            'global_step': int(global_step),
        }
        if epoch_losses:
            extras['epoch_losses'] = [float(v) for v in epoch_losses]
        return extras

    def build_payload(epoch, fetch):
        """The reference's checkpoint dict (+ resume-exactness extras).
        ``fetch=True`` gathers device arrays to host numpy for the msgpack
        writers — a collective every process must join; ``fetch=False``
        keeps device arrays for Orbax's shard-parallel IO."""
        weights = dense_params_view()
        opt_leaves = (None if pp_mode  # pp moments are stage-stacked
                      else jax.tree.leaves(opt_state))
        vae_weights = (vae_params
                       if is_custom_vae and vae_params is not None else None)
        if fetch:
            weights = host_fetch(weights)
            opt_leaves = (host_fetch(opt_leaves)
                          if opt_leaves is not None else None)
            vae_weights = (host_fetch(vae_weights)
                           if vae_weights is not None else None)
        payload = {
            'hparams': dalle_cfg.to_dict(),
            'vae_params': vae_hparams,  # None for pretrained VAEs (ref :167-172)
            'weights': weights,
            'scheduler': sched.state_dict(),
            'epoch': epoch,
        }
        if opt_leaves is not None:
            payload['opt_state'] = opt_leaves
        if vae_weights is not None:
            payload['vae_weights'] = vae_weights
        payload.update(resume_extras())
        return payload

    def save_model(path, epoch):
        if args.sharded_checkpoints:
            # Orbax writes each host's shards directly — no gather; every
            # process participates collectively
            from dalle_pytorch_tpu.utils.checkpoint import \
                save_checkpoint_sharded

            path = f'{path}.orbax'
            save_checkpoint_sharded(path, build_payload(epoch, fetch=False))
            return path
        # every process participates in the fetch (sharded params span
        # non-addressable devices multi-host); only root writes
        payload = build_payload(epoch, fetch=True)
        if not distr_backend.is_root_worker():
            return path
        save_checkpoint(path, payload)
        return path

    last_managed = [-1]  # step of the last managed-save attempt

    def save_managed(step, epoch):
        """Managed checkpoint: ckpt_dir/ckpt-{step:08d}/ with an integrity
        manifest, retried with backoff on transient I/O errors.  A failed
        save is logged, not fatal — the run survives and the next cadence
        (or the interrupt path) writes the next one."""
        if manager is None or step == last_managed[0]:
            return
        last_managed[0] = step
        payload = build_payload(epoch, fetch=not args.sharded_checkpoints)
        if args.sharded_checkpoints or distr_backend.is_root_worker():
            try:
                manager.save(step, payload)
            except OSError as e:
                print(f'[ckpt] managed save at step {step} failed after '
                      f'retries: {e}', file=sys.stderr, flush=True)
        # the ckpt phase watermark: the host-fetched payload is the
        # predicted timeline's snapshot term, live right here
        mem_tracker.snapshot('ckpt', step=step)

    from dalle_pytorch_tpu.utils.profiling import StepTimer, dalle_train_flops

    # BATCH_SIZE is per-host (the loader shards by process); StepTimer's
    # peak spans every chip of every process, so feed it global-batch FLOPs
    timer = StepTimer(flops_per_step=dalle_train_flops(
        dalle_cfg, BATCH_SIZE * jax.process_count()))
    # phase-boundary memory watermarks (obs/mem.py, the managed polling
    # surface): "init" here — params + opt state resident, no step run
    # yet — then once per epoch ("step_peak") and after each managed
    # save ("ckpt"), matching the ledger's predicted phase timeline.
    # Never per step: live_arrays() walks every buffer in the process.
    mem_tracker = obs_mem.MemTracker()
    mem_tracker.snapshot('init', step=start_step)
    lr = sched.lr
    global_step = start_step
    # managed on-chip trace window (steps 10-20 of the first trained
    # epoch, past compile + warmup), root process only.  --profile_dir is
    # the legacy alias of --xprof_dir; both route through prof.capture so
    # the trace rides a prof.xprof telemetry span (graftlint OBS003).
    xprof = prof.XprofWindow(
        logdir=args.xprof_dir or args.profile_dir,
        start=min(10, max(len(dl) - 2, 0)),
        stop=min(20, max(len(dl) - 1, 1)))
    if not distr_backend.is_root_worker() or len(dl) < 2:
        xprof.logdir = None  # root-only, like the legacy window
    # preemption-safe shutdown + stall detection (SURVEY.md §5.3 — the
    # reference has neither): SIGTERM/SIGINT checkpoint-and-stop, heartbeat
    # files for external monitors, in-process hung-step watchdog
    stopper = GracefulShutdown()
    heartbeat = (Heartbeat(args.heartbeat_dir,
                           stall_timeout=args.stall_timeout or None,
                           run_id=logger.run_name)
                 if args.heartbeat_dir else None)
    # anomaly policy over the per-step health vectors + hung-step watchdog
    monitor_h = (guardrails.HealthMonitor(
        mode='rollback' if args.health == 'rollback' else
             ('warn' if args.health == 'warn' else 'skip'),
        spike_zscore=args.spike_zscore) if health_on else None)
    watchdog = (guardrails.StepWatchdog(args.step_deadline)
                if args.step_deadline > 0 else None)
    if skip_past is not None and distr_backend.is_root_worker():
        print(f'[guardrails] rollback resume: skipping the data window '
              f'through step {skip_past} (steps {start_step + 1}..'
              f'{skip_past} consumed without updates)')
    interrupted = False
    t0 = time.perf_counter()
    completed = False
    try:
        with stopper:
            for epoch in range(start_epoch, EPOCHS):
                # in-place: the save closures hold this list object.  The
                # first resumed epoch keeps its restored partial losses so
                # the epoch-end plateau step sees the full epoch.
                epoch_losses[:] = (resume_epoch_losses
                                   if epoch == start_epoch else [])
                # one-step-deferred loss logging: materializing the loss each step
                # would block the host on the device (and the device on the host's
                # data loading + log IO).  The pmean dispatch is async; float() of
                # step i's loss happens after step i+1 is already in flight.
                pending = None  # (iter index, device loss)
                # collective stop flag, updated by flush: the preemption
                # check rides the per-step loss collective (one host
                # collective per step, not two)
                stop_poll = [False]

                def flush(pending):
                    if pending is None:
                        return
                    it, sid, loss_dev, hv = pending
                    # average_all here, not at dispatch: the multi-host impl blocks
                    # (process_allgather), which would kill the one-step deferral
                    avg_loss, stop_poll[0] = stopper.average_and_poll(
                        distr_backend, loss_dev)
                    perf = timer.tick(BATCH_SIZE * jax.process_count(),
                                      stall_s=batches.last_wait_s)
                    if monitor_h is None or np.isfinite(avg_loss):
                        # a sentinel-skipped step left params untouched; its
                        # NaN must not poison the plateau epoch mean either
                        epoch_losses.append(avg_loss)
                    logger.step(epoch, it, avg_loss, lr, extra=perf)
                    tel = obs.get()
                    if tel is not None:
                        # the per-step record: timing/MFU/stall (StepTimer)
                        # + the health vector, emitted BEFORE the anomaly
                        # policy observes it so a rollback's health events
                        # causally follow their step in the stream
                        fields = dict(step=sid, epoch=epoch, it=it,
                                      loss=avg_loss, lr=lr, **perf)
                        if hv is not None:
                            fields.update(
                                grad_norm=float(hv['grad_norm']),
                                applied=float(hv['applied']))
                        tel.event('step', 'train', **fields)
                    if monitor_h is not None:
                        # every process sees the same avg_loss (collective)
                        # and the same SPMD health scalars, so the verdict —
                        # and any rollback escape — is collective too
                        monitor_h.observe(sid, loss=avg_loss,
                                          grad_norm=float(hv['grad_norm']),
                                          applied=float(hv['applied']))
                        if monitor_h.wants_rollback:
                            escalate(sid)

                def escalate(sid):
                    """Anomaly escalation: drop the post-mortem bundle, then
                    escape to main()'s rollback loop (--resume auto +
                    data-window skip + LR backoff, budget-bounded)."""
                    if distr_backend.is_root_worker():
                        guardrails.write_anomaly_bundle(
                            args.ckpt_dir, sid, {
                                'reason': monitor_h.rollback_reason,
                                'loss': monitor_h.last_loss,
                                'grad_norm': monitor_h.last_grad_norm,
                                'loss_history': monitor_h.history(),
                                'epoch': epoch,
                                'loader': batches.state_dict(),
                                'rng': [int(v) for v in
                                        np.asarray(jax.device_get(rng))],
                                'config_fingerprint':
                                    config_fingerprint(dalle_cfg.to_dict()),
                                'lr': lr})
                    raise guardrails.RollbackAndSkip(
                        sid, max_rollbacks=args.max_rollbacks,
                        reason=monitor_h.rollback_reason or 'anomaly')

                for i, ((text, images),
                        (text_b, images_b)) in enumerate(batches):
                    # `it` is the TRUE batch index in this epoch's
                    # permutation: a mid-epoch resume skips the consumed
                    # batches, so `i` restarts at 0 while the cadences
                    # (sampling, checkpoints, logs) must continue from
                    # where the interrupted run left off — bitwise replay
                    # depends on every rng split landing at the same `it`
                    it = i + (resume_cursor if epoch == start_epoch else 0)
                    if skip_past is not None and global_step < skip_past:
                        # rollback-and-skip: consume the anomalous data
                        # window without training on it; the rng stream
                        # still advances one split per skipped step so
                        # post-window draws stay deterministic
                        rng, _ = jax.random.split(rng)
                        global_step += 1
                        if heartbeat is not None:  # skipping is progress
                            heartbeat.beat(global_step, epoch=epoch,
                                           health_state='skipping-window')
                        continue
                    # profiler window (ref had no profiler at all —
                    # SURVEY.md §5.1): prof.XprofWindow opens/closes the
                    # managed capture around the step window
                    if xprof.armed and epoch == start_epoch:
                        was_active = xprof.active
                        xprof.on_step(
                            i, sync=lambda: jax.block_until_ready(params))
                        if was_active and not xprof.active:
                            print('profiler trace written to '
                                  f'{xprof.logdir}')
                    if watchdog is not None:
                        # armed across the whole step iteration (dispatch,
                        # previous step's host sync, periodic sample/save) —
                        # any of them can wedge inside a device call
                        watchdog.arm(global_step + 1)
                    rng, step_rng = jax.random.split(rng)
                    if health_on:
                        params, opt_state, loss, health_vec = train_step(
                            params, opt_state, vae_params, text_b, images_b,
                            step_rng,
                            jnp.float32(guardrails.fault_scale_for(
                                global_step + 1)))
                    else:
                        health_vec = None
                        params, opt_state, loss = train_step(
                            params, opt_state, vae_params, text_b, images_b,
                            step_rng)
                    # chaos rehearsal: GRAFT_FAULTS="step_hang:at_step=N"
                    # wedges here, inside the watchdog's armed window
                    faults.maybe_hang(global_step + 1)

                    flush(pending)
                    # raw device loss + health; averaged/classified lazily
                    pending = (it, global_step + 1, loss, health_vec)

                    just_checkpointed = it % 100 == 0
                    if just_checkpointed:
                        # periodic sample (ref :396-412): SPMD computation, so every
                        # process runs it; only root writes the image.  The
                        # caption must be globally consistent — each host's
                        # loader yields different rows, and feeding divergent
                        # "replicated" inputs to one SPMD program is undefined
                        rng, gen_rng = jax.random.split(rng)
                        sample_text = text[:1].astype(np.int32)
                        if jax.process_count() > 1:
                            from jax.experimental import multihost_utils

                            sample_text = multihost_utils.broadcast_one_to_all(
                                sample_text)
                        sample_text = jnp.asarray(sample_text)
                        codes = generate_codes(dalle_dense,
                                               {'params': dense_params_view()},
                                               sample_text, gen_rng, filter_thres=0.9)
                        image = host_fetch(decode_images(vae_params, codes)[0])
                        if distr_backend.is_root_worker():
                            save_image(f'samples/dalle/epoch{epoch}_iter{it}.png', image)
                            decoded = tokenizer.decode(np.asarray(text[0]))
                            logger.log({'image_caption': decoded})
                        save_model('./dalle.pt', epoch)
                        # wandb.save parity (ref :409); no-op for .orbax dirs
                        logger.save_file('./dalle.pt')
                    global_step += 1
                    if args.ckpt_every > 0 and it % args.ckpt_every == 0:
                        # flush first so the checkpointed epoch_losses
                        # include THIS step — a resumed run's epoch mean
                        # must match the uninterrupted one bitwise
                        flush(pending)
                        pending = None
                        save_managed(global_step, epoch)
                    if heartbeat is not None:
                        # health extras ride every beat so tools/monitor.py
                        # can flag a sick run without reading logs; the
                        # loader stall rides too, so an input-bound run is
                        # visible in monitor output
                        heartbeat.beat(global_step, epoch=epoch, loss_iter=it,
                                       loader_stall_s=round(
                                           batches.last_wait_s, 4),
                                       **(monitor_h.beat_extras()
                                          if monitor_h is not None else {}))
                    if watchdog is not None:
                        watchdog.disarm()
                    # chaos rehearsal: GRAFT_FAULTS="sigterm:at_step=N"
                    # delivers a real preemption notice at step N;
                    # "preempt:at_step=N" additionally arms the bounded
                    # grace window (grace_ms) — miss it and the process is
                    # hard-killed with ExitCode.PREEMPT_EXPIRED, exactly
                    # like a scheduler's follow-up SIGKILL
                    faults.maybe_kill(global_step)
                    faults.maybe_preempt(global_step)
                    # multi-process: the collective decision from the last
                    # flush (every process saw the same 2-vector, so every
                    # process breaks at the same step — the collective save
                    # below cannot deadlock); single-process: the local flag,
                    # which is fresher by one step
                    if stop_poll[0] if jax.process_count() > 1 \
                            else stopper.requested:
                        flush(pending)
                        pending = None
                        resume_path = ('./dalle.pt.orbax' if args.sharded_checkpoints
                                       else './dalle.pt')
                        if not just_checkpointed:  # ./dalle.pt is already current
                            resume_path = save_model('./dalle.pt', epoch)
                        # final managed checkpoint for --resume auto (no-op
                        # if this step's cadence save already ran — a torn
                        # result there models dying mid-write, and resume
                        # must fall back, not paper over it)
                        save_managed(global_step, epoch)
                        if distr_backend.is_root_worker():
                            print(f'interrupted at epoch {epoch} iter {it}: resume '
                                  f'checkpoint written to {resume_path} '
                                  f'(--dalle_path {resume_path} to continue; '
                                  f'--resume auto picks the newest valid '
                                  f'managed checkpoint)')
                        interrupted = True
                        break
                flush(pending)
                if interrupted:
                    break

                # per-epoch plateau step on the epoch-mean loss (ref :415-416)
                epoch_loss = float(np.mean(epoch_losses)) if epoch_losses else float('inf')
                lr = sched.step(epoch_loss)
                opt_state = set_learning_rate(opt_state, lr)
                if epoch % 19 == 0:
                    # epoch + 1: this save happens AFTER the epoch-end
                    # plateau step, so a resume from it starts the next
                    # epoch instead of replaying this one
                    save_model(f'./sweep1/{logger.run_name}-{epoch}.pt',
                               epoch + 1)
                if distr_backend.is_root_worker():
                    dt = time.perf_counter() - t0
                    print(f'epoch {epoch} done: loss {epoch_loss:.4f} lr {lr:.2e} '
                          f'({dt:.1f}s elapsed)')
                # steady-state watermark once per epoch: the train-loop
                # residents (params/opt/prefetch) against the HBM limit
                mem_tracker.snapshot('step_peak', step=global_step,
                                     epoch=epoch)

            completed = not interrupted
    finally:
        # a death inside the trace window must still stop the profiler
        # (and close its telemetry span) before the stream shuts down
        xprof.close()
        if manager is not None:
            # join the in-flight async checkpoint write: the process must
            # not exit (or report resume state) with an uncommitted save
            manager.finish()
        # the final save is committed (or was never started): disarm any
        # preemption grace timer so a graceful stop that landed inside the
        # window is not hard-killed moments after
        faults.cancel_preempt_grace()
        if watchdog is not None:
            watchdog.close()
        if heartbeat is not None:
            heartbeat.close(done=completed)
        # run_end folds the StepTimer reservoir percentiles (perf_summary)
        # so obs_report can show p50/p99 step time without replaying every
        # step record; shutdown() also makes rollback relaunches (which
        # re-enter _main in-process) re-init a fresh stream
        obs.emit('run', 'run_end', step=global_step,
                 completed=completed, interrupted=interrupted,
                 **timer.percentiles())
        obs.shutdown()
        if metrics_server is not None:
            metrics_server.close()

    if not interrupted:
        final_path = save_model('./dalle-final.pt', EPOCHS)
        if distr_backend.is_root_worker():
            # wandb artifact upload parity (ref train_dalle.py:430-437)
            logger.log_artifact(final_path, 'trained-dalle')
    logger.finish()


if __name__ == '__main__':
    main()
