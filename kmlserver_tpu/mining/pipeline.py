"""The batch mining job, end to end — parity with the reference's ``__main__``
orchestration (reference: machine-learning/main.py:421-484):

dataset list → rotation index → CSV read → vocab/aux artifacts →
baskets → device mining → recommendations artifact → history append +
invalidation-token rewrite — with the same printed progress/timing lines the
reference's report reads off the pod logs (Sao Paulo timestamps at :423,431;
"Time elapsed in rule generation" from :306-308; missing-songs counter
from :298-305).

Preemption-proofing (ISSUE 4) restructures the run into three checkpointed
phases (``mining/checkpoint.py``):

- **encode** — CSV read, vocab validation/aux maps, basket encoding;
- **mine**   — frequent-itemset mining + rule-tensor extraction (the
  device compute, the dominant cost at scale);
- **rules**  — expansion into the reference's pickle dict;
- **embed**  — (optional, ``embed_enabled``) ALS item-embedding training
  over the same baskets (``mining/als.py``) — the SECOND model family,
  published as ``embeddings.npz`` through the same manifest + lease path
  and checkpointed like any other phase, proving the artifact spine is
  model-agnostic plumbing rather than rule-specific.

After each phase the writer rank persists an atomic sha256-manifested
checkpoint keyed by a config+dataset fingerprint; a restarted job resumes
from the last completed phase and publishes bit-identical pickles, while a
stale or corrupt checkpoint self-retires to recompute. ALL artifact writes
now happen in one publication step AFTER the phases — a job that dies
mid-phase leaves the PVC's served artifact set untouched (the reference
wrote vocab artifacts early, so an eviction could strand a half-new set;
the READ contract — filenames, object shapes, token polling — is
unchanged). Publication itself is fenced by a heartbeat lease with a
monotonic fencing token (``io/artifacts.py PublicationLease``): a zombie
job superseded by the GitOps ``Replace`` resync aborts instead of tearing
what the newer run published; the manifest records the token. The
checkpoint store is retired after a successful publication.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax

from .. import faults
from ..config import BASE_INDEX, MiningConfig
from ..data.csv import read_tracks
from ..io import artifacts, registry
from ..observability import costmodel
from ..observability.jobmetrics import JobMetrics
from ..utils.timeutil import get_current_time_str, get_current_time_str_precise
from . import checkpoint as ckpt_mod
from . import vocab as vocab_mod
from .miner import MiningResult, mine


@dataclasses.dataclass
class JobSummary:
    dataset: str
    run_index: int
    n_rows: int
    n_playlists: int
    n_tracks: int
    n_songs_missing: int
    rule_generation_s: float
    token: str
    artifact_paths: dict[str, str]
    # phases skipped because a verified checkpoint covered them
    resumed_phases: tuple[str, ...] = ()
    # the publication lease's fencing token (None: lease disabled / reader)
    fencing_token: int | None = None
    # ALS embedding training wall clock (None: embed phase disabled)
    als_train_s: float | None = None
    # continuous freshness (ISSUE 10): set when this run published a delta
    # bundle instead of a full artifact set (the chain sequence number;
    # None = full publication)
    delta_seq: int | None = None


def _pickle_path(cfg: MiningConfig, filename: str) -> str:
    return os.path.join(cfg.pickles_dir, filename)


def _crash_site(phase: str) -> None:
    """Deterministic preemption stand-in: ``KMLS_FAULT_MINE_CRASH_PHASE``
    aborts the job right AFTER ``phase``'s checkpoint persisted — the
    restarted job must resume from it (chaos-tested at every phase)."""
    faults.fire(f"mine.crash.{phase}")


def _run_encode_phase(cfg: MiningConfig, selected: str) -> dict:
    """CSV read + vocab validation/aux maps + basket encoding."""
    import numpy as np

    table = read_tracks(selected, cfg.sample_ratio)
    print(
        f"Loaded {len(table)} rows, {table.n_playlists} playlists, "
        f"{table.n_tracks} unique tracks (CSV loader: {table.loader})"
    )
    artists = vocab_mod.validate_and_map_artists(table)
    repeated = vocab_mod.extract_repeated_track_names(table)
    info = vocab_mod.map_track_ids_to_info(table)
    best = vocab_mod.most_frequent_tracks(table, cfg.top_tracks_save_percentile)
    baskets = vocab_mod.build_baskets(table)
    return {
        "n_rows": len(table),
        "artists": artists,
        "repeated": repeated,
        "info": info,
        "best": best,
        "baskets": baskets,
        # pid ranks backing playlist_rows (CKPT_VERSION 4): the delta
        # base state (freshness/delta.py) extends these with appended
        # rows' pids, so an incremental run re-ranks without re-reading
        # the full CSV
        "pid_values": np.unique(table.pid),
    }


def _report_mining(result: MiningResult, cfg: MiningConfig) -> None:
    tensors = result.tensors
    if result.pruned_vocab is not None:
        print(
            f"Apriori pruning: {result.n_tracks} -> {result.pruned_vocab} "
            f"candidate tracks before pair counting"
        )
    print(f"Songs without recommendations: {tensors.n_songs_missing}")
    print(f"Time elapsed in rule generation: {result.duration_s:.2f}s")
    if result.phase_timings:
        from ..utils.profiling import format_phases

        print(format_phases(result.phase_timings).capitalize())
    if result.count_path:
        print(f"Pair-count path: {result.count_path}")
    if result.itemset_census is not None:
        census = ", ".join(
            f"len {k}: {'not enumerated' if v < 0 else v}"
            for k, v in sorted(result.itemset_census.items())
        )
        print(f"Frequent itemsets — {census}")
    if tensors.overflow_rows:
        print(
            f"WARNING: {tensors.overflow_rows} songs exceeded the "
            f"K_max={cfg.k_max_consequents} consequent capacity (truncated "
            f"to the highest-support rules)"
        )


def run_mining_job(
    cfg: MiningConfig,
    mesh: "jax.sharding.Mesh | None" = None,
    watchdog=None,
) -> JobSummary:
    print(f"Job starting at {get_current_time_str()}")

    # continuous freshness (ISSUE 10): with KMLS_DELTA_ENABLED and a
    # matching base state on the PVC, this run publishes an incremental
    # delta bundle instead of re-mining everything — freshness lag drops
    # from full-mine wall clock to the restricted recount. ANY
    # ineligibility (no base, rewritten prefix, config drift, chain cap,
    # multi-host gang) falls through to the full pipeline below; the
    # delta path never publishes an approximation.
    if cfg.delta_enabled:
        from ..freshness import delta as delta_mod

        # delta-route telemetry (the Job manifests arm KMLS_JOB_METRICS
        # alongside KMLS_DELTA_ENABLED): a delta publication must refresh
        # job_metrics.prom — freshness-timestamp dashboards alert on its
        # age, and most syncs in steady state ARE deltas. Constructed
        # before the run so an abort still records success=0; the
        # ineligible fallthrough constructs nothing on disk (JobMetrics
        # only writes on phase_done/finish) and the full path below
        # writes its own. Writer-rank gate kept for symmetry even though
        # eligibility rejects multi-host gangs.
        jm_delta = (
            JobMetrics(cfg.pickles_dir)
            if cfg.job_metrics and jax.process_index() == 0
            else None
        )
        try:
            res = delta_mod.run_delta_job(cfg, mesh=mesh)
        except delta_mod.DeltaIneligible as exc:
            print(f"Delta mining ineligible ({exc}); running the full pipeline")
        except BaseException:
            if jm_delta is not None:
                try:
                    # same abort discipline as the full path: success=0
                    # telemetry, never masking the real cause
                    jm_delta.finish(False)
                except Exception:
                    pass
            raise
        else:
            if jm_delta is not None:
                try:
                    jm_delta.phase_done("delta", res.duration_s)
                    if res.bundle_path:
                        # analytic cost attribution (ISSUE 12): the
                        # delta's device compute is the column-
                        # restricted recount C[R, :] over the combined
                        # baskets — same formula the serving MFU uses
                        flops, moved = costmodel.phase_cost(
                            "delta_recount",
                            p=res.n_playlists, v=res.n_tracks,
                            rows=res.n_touched,
                        )
                        jm_delta.note_phase_cost("delta", flops, moved)
                        jm_delta.note_artifact("delta", res.bundle_path)
                    jm_delta.finish(
                        True,
                        rule_generation_s=res.duration_s,
                        fencing_token=res.fencing_token,
                    )
                except Exception as exc:
                    # publication already succeeded — telemetry is
                    # best-effort, exactly like the full path's guard
                    print(
                        f"WARNING: success telemetry skipped "
                        f"({jm_delta.path}): {exc!r}"
                    )
            # quality loop (ISSUE 14): once the chain reaches
            # KMLS_DELTA_COMPACT_AFTER bundles, fold base ∘ chain into a
            # new base bundle WITHOUT a full re-mine. Never fails the
            # job: a skipped compaction keeps the chain, the next delta
            # re-triggers, and KMLS_DELTA_MAX_CHAIN stays the backstop.
            if res.bundle_path:
                from ..quality import lifecycle as lifecycle_mod

                lifecycle_mod.maybe_compact(cfg)
            print(f"Job finished at {get_current_time_str()}")
            return JobSummary(
                dataset=res.dataset,
                run_index=res.run_index,
                n_rows=res.n_new_rows,
                n_playlists=0,
                n_tracks=0,
                n_songs_missing=0,
                rule_generation_s=res.duration_s,
                token=res.base_token,
                artifact_paths=(
                    {"delta": res.bundle_path} if res.bundle_path else {}
                ),
                fencing_token=res.fencing_token,
                delta_seq=res.seq if res.bundle_path else None,
            )

    # model layout (KMLS_MODEL_LAYOUT): resolved ONCE here so the mine
    # and embed phases ride the SAME vocab-sharded mesh — a sharded
    # layout with no mesh (or the dp-major auto mesh) gets a vocab-major
    # 1xN mesh over the local devices; replicated leaves it untouched
    from ..parallel import layout as layout_mod

    mesh = layout_mod.mining_mesh(cfg, mesh)

    # Multi-host: every rank participates in the sharded compute (the
    # collectives need all processes), but only rank 0 touches the shared
    # PVC — duplicate history appends would corrupt the dataset rotation,
    # and concurrent artifact writes could tear what the API replicas read.
    is_writer = jax.process_index() == 0

    datasets = registry.get_dataset_list(cfg, persist=is_writer)
    run_index = registry.get_next_run_index(cfg, datasets)
    selected = datasets[run_index - BASE_INDEX]
    print(f"Selected dataset {run_index}/{len(datasets)}: {selected}")

    # checkpoint store keyed by config+dataset fingerprint; every rank
    # reads (identical skip decisions keep the collectives aligned), the
    # writer saves. The completed-phase set is snapshotted at open time.
    store = ckpt_mod.open_store(cfg, selected, run_index, writer=is_writer)
    resumed: list[str] = []

    # mining-side telemetry (ISSUE 9): per-phase progress/duration/bytes
    # rewritten atomically to pickles/job_metrics.prom as phases complete
    # — a preempted job leaves the telemetry of what it DID finish.
    # Writer rank only, same discipline as every other PVC write.
    jm = (
        JobMetrics(cfg.pickles_dir)
        if is_writer and cfg.job_metrics
        else None
    )

    def phase(name: str, compute):
        """Resume ``name`` from its checkpoint or compute + persist it.
        The crash fault site fires AFTER the save — exactly where a
        preemption that already banked the phase would land. Either way
        the phase's compute duration reaches the telemetry file: a
        resumed phase reports the ORIGINAL duration from the
        checkpoint's span annotation, flagged resumed=1."""
        payload = store.load(name) if store is not None else None
        if payload is not None:
            resumed.append(name)
            print(
                f"Resumed phase {name!r} from checkpoint "
                f"({store.age_s(name):.0f}s old)"
            )
            if jm is not None:
                jm.phase_done(name, store.duration_s(name), resumed=True)
            return payload
        t_phase = time.perf_counter()
        payload = compute()
        duration_s = time.perf_counter() - t_phase
        if store is not None:
            store.save(name, payload, duration_s=duration_s)
        if jm is not None:
            jm.phase_done(name, duration_s)
        _crash_site(name)
        return payload

    # the writer takes the publication lease BEFORE the expensive phases:
    # its heartbeats prove liveness for the whole mine, and a superseding
    # run (GitOps Replace) fences this one out before it can publish.
    lease = None
    if is_writer:
        # ENOSPC preflight BEFORE the expensive phases: estimate the
        # publication from the last manifest (0 on first run), reclaim
        # quarantine + orphaned temp files if short, and exit resumable
        # (75) rather than tear a publication hours from now. Retired
        # phase checkpoints are fair game — a full mine re-derives them.
        free = artifacts.ensure_free_space(
            cfg.pickles_dir,
            max(
                artifacts.estimate_publication_bytes(cfg.pickles_dir),
                cfg.disk_min_free_bytes,
            ),
            extra_dirs=(ckpt_mod.retired_dirs(cfg)),
        )
        print(f"Disk preflight: {free / (1 << 20):.0f} MiB free on PVC")
    if is_writer and cfg.lease_enabled:
        lease = artifacts.PublicationLease.acquire(
            cfg.pickles_dir,
            ttl_s=cfg.lease_ttl_s,
            heartbeat_interval_s=cfg.lease_heartbeat_interval_s or None,
            stall_fraction=cfg.lease_stall_fraction,
        )
        lease.start_heartbeat()
        print(f"Publication lease acquired (fencing token {lease.fencing_token})")

    try:
        encoded = phase("encode", lambda: _run_encode_phase(cfg, selected))
        baskets = encoded["baskets"]

        def _mine() -> MiningResult:
            if watchdog is not None:
                # collective guard: a dead/hung peer rank turns the mine's
                # collectives into a forever-hang — bound it
                with watchdog.guard("mine"):
                    return mine(baskets, cfg, mesh=mesh)
            return mine(baskets, cfg, mesh=mesh)

        result: MiningResult = phase("mine", _mine)
        _report_mining(result, cfg)
        tensors = result.tensors
        if jm is not None:
            jm.set_dataset(
                rows=encoded["n_rows"],
                playlists=result.n_playlists,
                tracks=result.n_tracks,
            )
            # the measured dispatch decision (ISSUE 13), surfaced as a
            # labeled gauge: which family counted + what decided it
            if result.count_path:
                jm.note_count_path(
                    result.count_path, result.count_path_source or "",
                )
            # analytic cost attribution (ISSUE 12): the mine phase's
            # dominant kernel is the pair-support contraction C = XᵀX
            # over the (possibly pruned) mined shape — leading-order,
            # same costmodel.phase_cost formula serving MFU uses. A
            # sparse-family mine (ISSUE 13) did nnz-proportional work
            # instead, and the attribution must say so.
            if result.count_path and result.count_path.startswith("sparse"):
                pruned_v = result.pruned_vocab or result.n_tracks
                flops, moved = costmodel.phase_cost(
                    "sparse_count",
                    events=result.sparse_events or 0,
                    nnz=encoded["n_rows"], v=pruned_v,
                )
            else:
                flops, moved = costmodel.phase_cost(
                    "support_count",
                    p=result.n_playlists, v=result.n_tracks,
                )
            jm.note_phase_cost("mine", flops, moved)

        rules_dict = phase(
            "rules", lambda: tensors.to_rules_dict(result.vocab_names)
        )

        # the second model family: ALS item embeddings over the SAME
        # baskets the rule miner consumed (reused from the encode
        # checkpoint on resume), trained as its own checkpointed phase
        emb_payload = None
        if cfg.embed_enabled:

            def _embed():
                from . import als

                # the second model family rides the same mesh: under the
                # sharded layout the item half-sweep partitions along the
                # vocab axis (ALX recipe) instead of training one-device
                return als.train_embeddings(baskets, cfg, mesh=mesh)

            emb_payload = phase("embed", _embed)
            if emb_payload.get("item_factors") is None:
                # HBM-fit guard declined to train (als.py): this
                # generation publishes rules-only — loudly, not silently
                print(f"ALS embed phase skipped: {emb_payload.get('skipped')}")
                emb_payload = None
            else:
                print(
                    f"ALS embeddings trained: rank {emb_payload['rank']}, "
                    f"{emb_payload['iters']} iters, final loss "
                    f"{emb_payload['final_loss']:.3f} "
                    f"({emb_payload['duration_s']:.2f}s)"
                )
                if jm is not None:
                    # analytic cost attribution (ISSUE 12): the embed
                    # phase is the ALS half-sweep loop — over the full
                    # dense interaction matrix, or (ISSUE 13) over its
                    # compressed nnz-proportional form
                    if emb_payload.get("storage") == "sparse":
                        flops, moved = costmodel.phase_cost(
                            "als_sweep_sparse",
                            nnz=emb_payload.get(
                                "nnz", len(baskets.playlist_rows)
                            ),
                            p=baskets.n_playlists, v=baskets.n_tracks,
                            r=emb_payload["rank"],
                            iters=emb_payload["iters"],
                        )
                    else:
                        flops, moved = costmodel.phase_cost(
                            "als_sweep",
                            p=baskets.n_playlists, v=baskets.n_tracks,
                            r=emb_payload["rank"],
                            iters=emb_payload["iters"],
                        )
                    jm.note_phase_cost("embed", flops, moved)

        # quality loop (ISSUE 14): offline ranking evaluation over a
        # deterministic held-out split — trains BOTH model families on
        # the train half and scores every serving mode through the
        # production kernels. Its own checkpointed phase (a preempted
        # job resumes past the double-train), payload = the
        # deterministic report published below.
        qual_report = None
        if cfg.eval_enabled:

            def _eval():
                from ..quality import eval as qual_mod

                return qual_mod.run_eval_phase(cfg, baskets, mesh=mesh)

            qual_report = phase("eval", _eval)

        # ---------- publication (writer only, lease-fenced) ----------
        paths: dict[str, str] = {}
        token = ""
        if is_writer:
            if lease is not None:
                # fence point 1: a zombie aborts BEFORE its first write
                lease.check()
            paths["artists_mapping"] = _pickle_path(cfg, cfg.artists_mapping_file)
            artifacts.save_pickle(encoded["artists"], paths["artists_mapping"])
            if encoded["repeated"]:
                # the reference saves this one conditionally (main.py:86-109)
                paths["repeated_tracks"] = _pickle_path(
                    cfg, cfg.repeated_tracks_file
                )
                artifacts.save_pickle(
                    encoded["repeated"], paths["repeated_tracks"]
                )
            paths["track_info"] = _pickle_path(cfg, cfg.track_info_file)
            artifacts.save_pickle(encoded["info"], paths["track_info"])
            paths["best_tracks"] = _pickle_path(cfg, cfg.best_tracks_file)
            artifacts.save_pickle(encoded["best"], paths["best_tracks"])
            print(
                f"Saved {len(encoded['best'])} best tracks "
                f"(top {cfg.top_tracks_save_percentile:.0%})"
            )

            # the token value is generated BEFORE the manifest so the
            # manifest can be stamped with the generation it describes —
            # readers validate only when the published token matches
            token_value = get_current_time_str_precise()
            paths["recommendations"] = _pickle_path(cfg, cfg.recommendations_file)
            artifacts.save_pickle(rules_dict, paths["recommendations"])
            if cfg.write_tensor_artifact:
                paths["rule_tensors"] = artifacts.tensor_artifact_path(
                    paths["recommendations"]
                )
                artifacts.save_rule_tensors(
                    paths["rule_tensors"],
                    vocab=result.vocab_names,
                    rule_ids=tensors.rule_ids,
                    rule_counts=tensors.rule_counts,
                    item_counts=tensors.item_counts,
                    n_playlists=result.n_playlists,
                    min_support=cfg.min_support,
                    mode=tensors.mode,
                    min_confidence=tensors.min_confidence,
                    rule_confs64=tensors.rule_confs64,
                )
            if emb_payload is None:
                # embed phase off: a previous generation's embeddings must
                # not survive into this publication's manifest, where they
                # would be re-blessed against rules they weren't trained on
                artifacts.remove_embeddings(cfg.pickles_dir)
            else:
                # second writer on the same spine: the embedding artifact
                # rides the identical atomic-write + manifest + fence
                # discipline as the rule tensors — a reader that can
                # validate one can validate the other
                paths["embeddings"] = artifacts.embeddings_artifact_path(
                    cfg.pickles_dir
                )
                artifacts.save_embeddings(
                    paths["embeddings"],
                    vocab=baskets.vocab.names,
                    item_factors=emb_payload["item_factors"],
                    rank=emb_payload["rank"],
                    iters=emb_payload["iters"],
                    reg=emb_payload["reg"],
                    final_loss=emb_payload["final_loss"],
                )
            if qual_report is None:
                # eval off this generation: a previous report must not
                # survive into this publication's manifest, where a
                # blend optimum measured against retired models would be
                # re-blessed (the embeddings-retirement precedent)
                artifacts.remove_quality_report(cfg.pickles_dir)
            else:
                # fourth writer on the same spine: the quality report
                # rides the identical atomic-write + manifest + fence
                # discipline as every other artifact
                paths["quality_report"] = artifacts.save_quality_report(
                    cfg.pickles_dir, qual_report
                )
            if cfg.write_manifest:
                # integrity sidecar AFTER the artifact set, BEFORE the token:
                # any reader that sees the new token sees a manifest matching
                # the new bytes; a reader racing mid-update detects the
                # mismatch and keeps serving its last-good bundle (engine.load
                # validates before publishing). Stamped with the token value
                # about to publish, so a LATER manifest-less writer (the
                # reference job) retires this manifest just by rewriting the
                # token — its fresh artifacts are never judged by stale sums.
                # The file set is quality/lifecycle.py's ONE copy, shared
                # with the compactor.
                from ..quality.lifecycle import manifest_filenames

                paths["manifest"] = artifacts.write_manifest(
                    cfg.pickles_dir,
                    manifest_filenames(cfg),
                    token=token_value,
                    fencing_token=lease.fencing_token if lease else None,
                )
            if lease is not None:
                # fence point 2: the last instant a zombie can be stopped
                # before the token rewrite makes its stale set authoritative
                lease.check()
            token = registry.append_history_and_invalidate(
                cfg, run_index, selected, timestamp=token_value
            )
            # continuous freshness: a FULL publication supersedes any
            # delta chain of the previous generation and seeds the next
            # incremental run with this run's encode state + tensors.
            # Best-effort — the artifacts above already published, so a
            # freshness bookkeeping failure must not fail the job (the
            # next run simply full-mines).
            if cfg.delta_enabled:
                from ..freshness import delta as delta_mod

                try:
                    artifacts.retire_delta_chain(cfg.pickles_dir)
                    npz_sha = None
                    if "rule_tensors" in paths:
                        npz_sha = artifacts.file_digest(
                            paths["rule_tensors"]
                        )["sha256"]
                    delta_mod.save_base_state(
                        cfg,
                        token=token_value,
                        run_index=run_index,
                        dataset_path=selected,
                        baskets=encoded["baskets"],
                        pid_values=encoded.get("pid_values"),
                        published=delta_mod.published_from_tensors(
                            tensors, result.vocab_names
                        ),
                        npz_sha256=npz_sha,
                    )
                    print("Freshness base state saved (delta mining armed)")
                except Exception as exc:
                    print(
                        f"WARNING: freshness base state skipped: {exc!r}"
                    )
            else:
                # delta mining off: a chain left by a previous
                # configuration must not outlive the generation it patched
                artifacts.retire_delta_chain(cfg.pickles_dir)
            if store is not None:
                # published: the next rotation run must start fresh
                store.clear()
            if jm is not None:
                # success telemetry LAST: artifact sizes of the set just
                # published, the fencing token that fenced it, success=1
                # + the freshness timestamp dashboards alert on. Broad
                # guard like the abort path below: publication already
                # succeeded, so nothing from telemetry (write() is
                # best-effort on OSError; registry-drift KeyError is the
                # other escape) may fail the job or skip lease.release()
                # — the abort handler would overwrite this very telemetry
                # with success=0 for a run that actually published.
                try:
                    for artifact_name, artifact_path in paths.items():
                        jm.note_artifact(artifact_name, artifact_path)
                    jm.finish(
                        True,
                        rule_generation_s=result.duration_s,
                        fencing_token=lease.fencing_token if lease else None,
                    )
                except Exception as exc:
                    print(
                        f"WARNING: success telemetry skipped "
                        f"({jm.path}): {exc!r}"
                    )
            if lease is not None:
                lease.release()
    except BaseException:
        if jm is not None:
            try:
                # the abort itself is telemetry: success=0 with the
                # completed phases' durations still on the PVC. write()
                # is already best-effort on OSError; the broad guard is
                # for anything else (registry-drift KeyError) — nothing
                # from telemetry may mask the real abort cause or keep
                # the lease release below from running.
                jm.finish(False)
            except Exception:
                pass
        if lease is not None:
            # a Python-level abort releases: this process writes nothing
            # more, and the replacement pod must not wait out the TTL.
            # Hard kills (SIGKILL preemption) skip this and expire instead.
            lease.stop_heartbeat()
            try:
                lease.release()
            except (artifacts.LeaseLostError, OSError):
                pass  # already fenced/unwritable: nothing to hand back
        raise
    finally:
        if lease is not None:
            lease.stop_heartbeat()
    print(f"Job finished at {get_current_time_str()}")

    return JobSummary(
        dataset=selected,
        run_index=run_index,
        n_rows=encoded["n_rows"],
        n_playlists=result.n_playlists,
        n_tracks=result.n_tracks,
        n_songs_missing=tensors.n_songs_missing,
        rule_generation_s=result.duration_s,
        token=token,
        artifact_paths=paths,
        resumed_phases=tuple(resumed),
        fencing_token=lease.fencing_token if lease else None,
        als_train_s=(
            emb_payload["duration_s"] if emb_payload is not None else None
        ),
    )
