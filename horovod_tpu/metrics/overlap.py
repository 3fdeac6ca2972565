"""Compiled-path overlap telemetry: bucket plans + measured overlap efficiency.

PR 1's headline feature — K reverse-backward-order gradient buckets issued
as independent psums so XLA's latency-hiding scheduler overlaps their ICI
transfer with the remaining backward compute — previously ran blind. Two
complementary instruments fix that:

1. **Plan gauges** (`record_plan`, fed from fusion.fused_allreduce at trace
   time): bucket count, per-bucket bytes in issue order, fusion-buffer
   occupancy vs the threshold, and a *planned* overlap-efficiency bound —
   the byte fraction that CAN be hidden. Bucket i's collective can overlap
   the compute that produces buckets i+1..K-1, so the hideable fraction is
   ``1 - bytes(last bucket)/total``: a single fused buffer (K=1) can hide
   nothing, and the bound rises monotonically as the tail bucket shrinks.

2. **Measured efficiency** (`measure_overlap`): run the step under
   ``jax.profiler.trace`` and read the profiler's ``.xplane.pb`` through
   ``metrics/device_profile.py`` — collective op spans (by HLO opcode) vs
   the union of the same device's other ops. ``overlap_efficiency`` = hidden
   collective time / total collective time. Requires a backend whose profile
   carries per-op device spans (TPU); on CPU hosts the report is
   ``ok=False`` and only the plan gauges are populated.

Both write the same registry, so a metrics snapshot carries
`horovod_overlap_*` gauges either way.
"""

from __future__ import annotations

import tempfile
from typing import Callable, Optional

from .registry import DEFAULT_BYTE_BUCKETS, registry

# Latest recorded plan, for tests and snapshot annotations: list of
# (issue_index, nbytes) in collective-issue order.
_last_plan: Optional[list] = None


def record_plan(plan, threshold: int, staged: bool = True) -> list:
    """Record a FusionPlan's bucket geometry into the registry (called from
    fusion.fused_allreduce at trace time — once per compile, not per step).

    ``staged``: whether the caller copies each bucket into a flat buffer
    (the hierarchical and sharded planners, whose reduce-scatter needs one
    divisible length a bucket). The flat data-parallel path hands a bucket's
    leaves to the collective as they are and passes False.

    Returns the recorded [(issue_index, nbytes), ...] list."""
    global _last_plan
    reg = registry()
    sizes = []
    for i, bucket in enumerate(plan.buckets):
        nbytes = sum(d.size * d.dtype.itemsize for d in bucket)
        if plan.pad_to > 1:
            elems = sum(d.size for d in bucket)
            rem = elems % plan.pad_to
            if rem:
                nbytes += (plan.pad_to - rem) * bucket[0].dtype.itemsize
        sizes.append((i, nbytes))
    planned_bytes = sum(n for _, n in sizes)
    total = planned_bytes or 1
    reg.gauge("horovod_fusion_buckets",
              help="buckets in the latest compiled fusion plan").set(len(sizes))
    reg.gauge("horovod_fusion_planned_bytes",
              help="total gradient bytes in the latest fusion plan").set(total)
    reg.gauge("horovod_fusion_staged_bytes",
              help="bytes a step copies into flat fusion buffers in the "
                   "latest plan, padding included (0 = the leaves go to the "
                   "collective as they are)").set(
        planned_bytes if staged else 0)
    occ = reg.gauge("horovod_fusion_buffer_occupancy",
                    help="largest bucket bytes / fusion threshold")
    occ.set(max(n for _, n in sizes) / max(1, threshold))
    hist = reg.histogram("horovod_fusion_bucket_bytes",
                         help="per-bucket byte sizes across recorded plans",
                         buckets=DEFAULT_BYTE_BUCKETS)
    for _, n in sizes:
        hist.observe(n)
    planned = 0.0
    if plan.reverse_order and len(sizes) > 1:
        planned = 1.0 - sizes[-1][1] / total
    reg.gauge(
        "horovod_overlap_efficiency_planned",
        help="byte fraction of the bucketed allreduce that the plan allows "
             "XLA to hide under backward compute (0 = single fused buffer)",
    ).set(planned)
    _last_plan = sizes
    return sizes


def last_plan() -> Optional[list]:
    """[(issue_index, nbytes), ...] of the most recently recorded plan."""
    return _last_plan


# Latest wire-compression plan: (compression, [(orig_nbytes, compressed?,
# wire_nbytes), ...]) in bucket-issue order (tests + snapshot annotations).
_last_wire_plan: Optional[tuple] = None


def record_wire_plan(compression: str, buckets: list) -> list:
    """Record a fused_allreduce call's per-bucket wire-compression verdicts
    (ISSUE 5). Runs at TRACE time, once per compile; the gauges describe the
    PER-STEP wire cost of the latest compiled plan (counters would double
    count across recompiles — the eager/native planes own the
    ``horovod_wire_bytes_total`` counters, the compiled plane is static).

    ``buckets``: [(orig_nbytes, compressed?, wire_nbytes), ...]."""
    global _last_wire_plan
    reg = registry()
    wire_on = [(n, w) for n, c, w in buckets if c]
    sent = sum(w for _, w in wire_on) + sum(
        n for n, c, _ in buckets if not c)
    saved = sum(n - w for n, w in wire_on)
    reg.gauge(
        "horovod_compiled_wire_bytes_per_step",
        help="gradient bytes per step the latest compiled plan puts on the "
             "wire (after per-bucket compression)").set(sent)
    reg.gauge(
        "horovod_compiled_wire_bytes_saved_per_step",
        help="gradient bytes per step the wire dtype saves vs uncompressed "
             "in the latest compiled plan").set(saved)
    reg.gauge(
        "horovod_compiled_wire_buckets",
        help="buckets riding the compressed wire in the latest plan"
    ).set(len(wire_on))
    reg.set_info("wire_compression", {
        "compression": compression, "buckets": len(buckets),
        "compressed_buckets": len(wire_on)})
    _last_wire_plan = (compression, list(buckets))
    return buckets


def last_wire_plan() -> Optional[tuple]:
    """(compression, [(orig_nbytes, compressed?, wire_nbytes), ...]) of the
    most recent fused_allreduce trace."""
    return _last_wire_plan


def record_flash_plan(live: int, masked: int, bwd_sub_tiles: int,
                      bwd_skipped: int,
                      grid_steps: Optional[int] = None,
                      shared_key_lanes: int = 0) -> float:
    """Record how the latest traced flash-attention call splits its work
    (trace time, once per compile — same reasoning as record_wire_plan;
    ``ops.flash_attention.block_census`` counts all four). ``live``: block
    steps a head executes; ``masked``: those the causal diagonal crosses,
    which build and apply the mask. The first gauge is the share that runs
    the forward's unmasked body: 0 for one block per row, 1 for non-causal
    attention. ``bwd_sub_tiles``: the sub-tiles the backward's two kernels
    walk those live blocks in; ``bwd_skipped``: those wholly above the
    diagonal, which they never compute. The second gauge is their share: 0
    for non-causal attention, largest for one block per row. ``grid_steps``
    (causal-dense calls alone; a windowed or non-causal call leaves the
    third gauge as it was): the steps a head of the forward's grid really
    holds, live or not. The third gauge is the share of them that run
    nothing: 0 where the grid is the folded triangle of an even number of q
    blocks or one block, ``1 / (nq + 1)`` for an odd number.
    ``shared_key_lanes``: the width of the call's shared key part
    (``flash_attention(k_shared=)``: latent attention's rotary key, read as
    ONE head through an index map), 0 for a call without one: the fourth
    gauge."""
    share = (live - masked) / max(1, live)
    registry().gauge(
        "horovod_flash_unmasked_block_share",
        help="share of the latest traced flash forward's live k-block steps "
             "that lie wholly below the causal diagonal and skip the mask"
    ).set(share)
    registry().gauge(
        "horovod_flash_bwd_skipped_subtile_share",
        help="share of the sub-tiles of the latest traced flash backward's "
             "live blocks that lie wholly above the causal diagonal and are "
             "skipped"
    ).set(bwd_skipped / max(1, bwd_sub_tiles))
    if grid_steps is not None:
        registry().gauge(
            "horovod_flash_dead_step_share",
            help="share of the grid steps a head of the latest traced "
                 "causal-dense flash forward holds that run nothing"
        ).set((grid_steps - live) / max(1, grid_steps))
    registry().gauge(
        "horovod_flash_shared_key_lanes",
        help="lanes of the key part that ONE head holds for every head of "
             "the latest traced flash call (latent attention's rotary key); "
             "0 for a call whose keys are each head's own"
    ).set(shared_key_lanes)
    return share


def record_flash_window_plan(live: int, dense_live: int) -> float:
    """Record what the latest traced WINDOWED flash-attention call saves
    (trace time, once per compile): the block steps a head executes under
    its window over those of the causal-dense call at the same blocks
    (``ops.flash_attention.block_census`` counts both). 1 would be a window
    that reaches every key; 2 T / (window + block) is about what a band a
    block or so wide comes to."""
    share = live / max(1, dense_live)
    registry().gauge(
        "horovod_flash_window_block_share",
        help="block steps of the latest traced windowed flash forward over "
             "those of the causal-dense call at the same blocks"
    ).set(share)
    return share


def record_chunked_loss_plan(products: int) -> None:
    """Record how many vocabulary products a chunk the latest traced
    ``models.transformer.chunked_lm_loss`` issues (trace time, once per
    compile — same reasoning as record_wire_plan): 1 when the call is not
    differentiated (logits), 3 under ``jax.grad`` (logits, d-hidden,
    d-kernel, all in the forward loop; no logits computed again)."""
    registry().gauge(
        "horovod_chunked_loss_products_per_chunk",
        help="vocabulary products a chunk of the latest traced "
             "chunked_lm_loss: 1 not differentiated, 3 with its gradients"
    ).set(products)


def record_moe_grouped_plan(border_overhead: float, weight_itemsize: int,
                            lookahead_share: float) -> None:
    """Record which path the latest traced ``ops.moe.dropless_experts`` gave
    its grouped products (trace time, once per compile): the worst-case row
    blocks multiplied over row blocks of work, ``(B + E - 1) / B`` with ``B =
    rows / 128`` (``ops.grouped_matmul.border_overhead``), of the repo's
    kernels, or 0 where the shapes kept ``lax.ragged_dot``; and the itemsize
    of the weights the kernels read: 4 the float32 parameters themselves,
    rounded in VMEM, 2 a bf16 copy someone cast, 0 under ``lax.ragged_dot``
    (whose weights are cast beforehand, ``hvd_moe_weight_cast``); and the
    share of a rows x weights call's weight-block fetches that the kernel
    starts a whole group ahead, under that group's products
    (``ops.grouped_matmul.lookahead_share``: ``(E - 1) / E``, all but the
    first of a column tile), 0 under ``lax.ragged_dot``."""
    registry().gauge(
        "horovod_moe_grouped_border_overhead",
        help="worst-case row blocks multiplied over row blocks of work of the "
             "grouped-product kernels in the latest traced dropless_experts; "
             "0 = lax.ragged_dot"
    ).set(border_overhead)
    registry().gauge(
        "horovod_moe_grouped_weight_itemsize",
        help="bytes a weight the grouped-product kernels of the latest traced "
             "dropless_experts read: 4 = the f32 parameters, rounded in VMEM; "
             "2 = a cast copy; 0 = lax.ragged_dot"
    ).set(weight_itemsize)
    registry().gauge(
        "horovod_moe_grouped_weight_lookahead_share",
        help="share of the weight-block fetches of a rows x weights kernel of "
             "the latest traced dropless_experts that start a whole group "
             "ahead: (E - 1) / E; 0 = lax.ragged_dot"
    ).set(lookahead_share)


def record_moe_dispatch_rows(rows: int, row_bytes: int) -> None:
    """Record the sorted rows the passes of the latest traced
    ``ops.moe.dropless_experts`` visit (trace time, once per compile): ``N x
    top_k`` where the rank holds every expert; where it holds ``count`` of
    ``of``, its share ``N x top_k x count / of`` of them in whole windows
    (``ops.moe.held_window_rows``): what a balanced router makes the passes
    move. The rows a step really visits follow the routing (the layer sows
    them as ``moe_live_rows``). ``row_bytes`` is what ONE dispatched row
    holds (``horovod_moe_dispatch_row_bytes``): the width the experts live
    in times the activations' itemsize - 4,096 for bf16 rows of a 2,048-wide
    model, 2,048 where the experts live in a 1,024-wide latent. It is what
    an expert-parallel ``all_to_all`` would carry a pair."""
    registry().gauge(
        "horovod_moe_dispatch_row_bytes",
        help="bytes of one row the latest traced dropless_experts dispatches "
             "(the experts' input width x the activations' itemsize); 0 = "
             "none traced"
    ).set(row_bytes)
    registry().gauge(
        "horovod_moe_dispatch_rows",
        help="sorted rows the passes of the latest traced dropless_experts "
             "visit per layer under a balanced router: N x top_k with every "
             "expert held, the held share in whole windows else; 0 = none "
             "traced"
    ).set(rows)


def record_moe_router_saved_bytes(saved_bytes: int) -> None:
    """Record what the backward of the latest traced
    ``ops.moe.sigmoid_route_tokens`` keeps of its router (trace time, once
    per compile, from the call's own shapes): the bytes of the two arrays it
    names, the sigmoid scores at the chosen experts and the weights, ``2 x N
    x top_k x 4`` - where plain autodiff kept the ``N x E`` scores. A caller
    that recomputes the layer saves them (with the chosen experts) and runs
    no router again; 0 until a sigmoid-routed layer is traced."""
    registry().gauge(
        "horovod_moe_router_saved_bytes_per_layer",
        help="bytes of the chosen scores and the weights, (N, top_k) float32 "
             "each, that the latest traced sigmoid router names for its "
             "backward (one call = one layer); 0 = none traced"
    ).set(saved_bytes)


def record_moe_router_recomputed(recomputed: bool) -> None:
    """Record whether the backward pass of the latest traced sigmoid-routed
    layer runs its router again (trace time, once per compile). The forward
    rule of ``ops.moe.sigmoid_route_tokens`` says 1 where ``jax.checkpoint``
    recomputes the layer; a policy of ``ops.moe.save_names`` that holds
    ``ops.moe.ROUTER_SAVED`` says 0 when it is asked about the layer's
    chosen scores, which is after (``TransformerLM(remat=True)``). So 1 =
    recomputed under a policy that does not save them by that function: four
    float32 router products a layer and not three."""
    registry().gauge(
        "horovod_moe_router_recomputed",
        help="1 = the latest traced sigmoid-routed layer is recomputed "
             "(jax.checkpoint) under a policy that does not save its router's "
             "names (ops.moe.save_names): its router runs again in the "
             "backward pass; 0 = saved, or nothing recomputed"
    ).set(int(recomputed))


def record_moe_live_rows(live_rows, window: int) -> None:
    """Record what the expert layers' passes REALLY visited in the steps a
    training loop hands in: ``live_rows`` is (steps, layers) of the
    ``moe_live_rows`` each layer sowed in each step, CONCRETE (read on the
    host between steps, by a logging hook or a registry collector; never
    from inside a jitted step). ``horovod_moe_live_rows_per_step`` is the
    layers' sum a step, its mean over the steps;
    ``horovod_moe_live_windows_per_step`` the windows of ``window`` rows that
    hold them (one pass of every layer: the trip counts of the held path's
    loops). Beside ``horovod_moe_dispatch_rows`` (a balanced router's rows a
    layer, from shapes) they say how far the routing is from balance."""
    steps = [[int(rows) for rows in step] for step in live_rows]
    if not steps:
        return
    registry().gauge(
        "horovod_moe_live_rows_per_step",
        help="rows on the held experts that the expert layers' passes "
             "visited a step (sum over layers, mean over the recorded steps)"
    ).set(sum(map(sum, steps)) / len(steps))
    registry().gauge(
        "horovod_moe_live_windows_per_step",
        help="windows of sorted rows holding those live rows, one pass of "
             "every expert layer (mean over the recorded steps)"
    ).set(sum(-(-rows // window) for step in steps for rows in step)
          / len(steps))


def record_dsa_census(census, dense_steps: int) -> None:
    """Record what the sparse-attention layers REALLY selected in the steps a
    training loop hands in: ``census`` is (steps, 2) of the selected (query,
    key) pairs and the live block steps a head of the selected flash kernels
    ran, each summed over a step's layers from what the layers sowed
    (``dsa_selected_pairs``, ``dsa_live_block_steps``), CONCRETE (read on the
    host between steps, never from inside a jitted step). ``dense_steps``:
    the block steps of the causal-dense call at the same blocks, over the
    same layers. Three gauges, each the mean over the recorded steps, and
    ``horovod_flash_dead_step_share`` as the selection leaves it: the share
    of the causal-dense grid's steps in which no pair is selected, which
    fetch and run nothing."""
    steps = [[int(v) for v in step] for step in census]
    if not steps:
        return
    pairs = sum(step[0] for step in steps) / len(steps)
    live = sum(step[1] for step in steps) / len(steps)
    registry().gauge(
        "horovod_dsa_selected_pairs_per_step",
        help="(query, key) pairs the sparse-attention layers selected a step "
             "(sum over layers and rows, mean over the recorded steps)"
    ).set(pairs)
    registry().gauge(
        "horovod_dsa_live_block_steps_per_step",
        help="block steps a head of the selected flash kernels ran a step: "
             "those with a selected pair (sum over layers and rows, mean over "
             "the recorded steps)"
    ).set(live)
    registry().gauge(
        "horovod_dsa_dense_block_steps_per_step",
        help="block steps of the causal-dense flash call at the selected "
             "kernels' blocks, over the same layers and rows"
    ).set(dense_steps)
    registry().gauge(
        "horovod_flash_dead_step_share",
        help="share of the grid steps a head of the latest traced "
             "causal-dense flash forward holds that run nothing"
    ).set((dense_steps - live) / max(1, dense_steps))


def record_dsa_select_plan(visited_share: float) -> None:
    """Record how far the latest traced ``ops.sparse_attention.select`` stops
    at the causal diagonal (trace time, once per compile, from shapes): the
    key columns the selection kernel's counting passes visit over queries x
    keys (``ops.sparse_attention.select_share``: a row tile visits the key
    chunks at or before its last query). About a half on a long row."""
    registry().gauge(
        "horovod_dsa_select_visited_share",
        help="key columns the counting passes of the latest traced "
             "sparse-attention selection visit, over queries x keys; 0 = "
             "none traced"
    ).set(visited_share)


def record_ssd_plan(chunk: int, kernel: bool) -> None:
    """Record the chunk length the latest traced ``ops.ssd.ssd`` cut its rows
    into (trace time, once per compile): the configured chunk, or the row's
    own length where that is shorter. 0 until a state-space scan is traced.
    ``kernel`` says whether its shapes took the scan's kernels:
    ``horovod_ssd_kernel_scans`` counts the traced scans that did since the
    latest one that kept ``jax.numpy``, which sets it back to 0."""
    registry().gauge(
        "horovod_ssd_chunk_len",
        help="positions a chunk of the latest traced ops.ssd.ssd (the "
             "chunked state-space scan); 0 = none traced"
    ).set(chunk)
    scans = registry().gauge(
        "horovod_ssd_kernel_scans",
        help="traced ops.ssd.ssd calls whose shapes took the scan's kernels "
             "(hvd_ssd_scan_fwd / _bwd) since the latest one that kept "
             "jax.numpy; 0 = none traced, or the latest kept jax.numpy")
    if kernel:
        scans.inc()
    else:
        scans.set(0)


def record_kda_plan(chunk: int, saved_state_bytes: int, kernel: bool) -> None:
    """Record what the latest traced ``ops.kda.kda`` cut its rows into (trace
    time, once per compile, from the call's own shapes): the chunk length
    (the configured one, or the row's own where that is shorter) and the
    bytes its backward keeps of the carried states (the state each block of
    chunks starts from; 0 would mean they are recomputed). Both 0 until a
    delta-rule scan is traced. ``kernel``: whether the shapes took the scan's
    pallas kernels; ``horovod_kda_kernel_scans`` counts the traced scans that
    did since the latest one that kept ``jax.numpy``, which sets it back to
    0."""
    registry().gauge(
        "horovod_kda_chunk_len",
        help="positions a chunk of the latest traced ops.kda.kda (the "
             "chunked gated delta rule); 0 = none traced"
    ).set(chunk)
    registry().gauge(
        "horovod_kda_saved_state_bytes_per_layer",
        help="bytes of carried states the backward of the latest traced "
             "ops.kda.kda keeps (one call = one layer); 0 = none traced"
    ).set(saved_state_bytes)
    scans = registry().gauge(
        "horovod_kda_kernel_scans",
        help="traced ops.kda.kda calls whose shapes took the scan's kernels "
             "(hvd_kda_scan_fwd / _bwd) since the latest one that kept "
             "jax.numpy; 0 = none traced, or the latest kept jax.numpy")
    if kernel:
        scans.inc()
    else:
        scans.set(0)


def record_kda_fused_mixer(fused: bool) -> None:
    """Record whether the latest traced ``models.kda.KDAMixer`` ran its gate
    and its output norm as ``ops.kda_fused``'s kernels on the (B, T, H x 128)
    form (trace time, once per compile): ``horovod_kda_fused_mixers`` counts
    the traced mixers that did since the latest one that kept ``jax.numpy``,
    which sets it back to 0 (``horovod_kda_kernel_scans``' rule)."""
    mixers = registry().gauge(
        "horovod_kda_fused_mixers",
        help="traced KDAMixers whose gate and output norm took the fused "
             "kernels (hvd_kda_gate_fwd / _bwd, hvd_kda_out_norm_fwd / _bwd) "
             "since the latest one that kept jax.numpy; 0 = none traced, or "
             "the latest kept jax.numpy")
    if fused:
        mixers.inc()
    else:
        mixers.set(0)


def record_kda_beta_range(upper: int) -> None:
    """Record the range of beta in the latest traced ``models.kda.KDAMixer``
    (trace time, once per compile): 2 where the mixer doubles the sigmoid
    (``KDADims.allow_neg_eigval``: beta in (0, 2), the delta rule's
    transition with an eigenvalue in (-1, 1)), 1 where beta is the sigmoid
    itself. 0 until a mixer is traced."""
    registry().gauge(
        "horovod_kda_beta_range",
        help="upper end of beta's range in the latest traced KDAMixer: 2 = "
             "twice the sigmoid (negative eigenvalues allowed), 1 = the "
             "sigmoid; 0 = none traced"
    ).set(upper)


def record_gdn_plan(chunk: int, saved_state_bytes: int, key_lanes: int) -> None:
    """Record what the latest traced ``ops.gdn.gdn`` (the gated delta rule
    with one decay a head) cut its rows into, by ``record_kda_plan``'s rules
    (trace time, once per compile, from the call's own shapes): the chunk
    length, the bytes its backward keeps of the carried states, and the lanes
    a head's keys take where the rule runs (128 in the kernels, zero lanes
    after the head's own; the head's own width in ``jax.numpy``). All 0 until
    such a scan is traced."""
    registry().gauge(
        "horovod_gdn_chunk_len",
        help="positions a chunk of the latest traced ops.gdn.gdn (the gated "
             "delta rule with one decay a head); 0 = none traced"
    ).set(chunk)
    registry().gauge(
        "horovod_gdn_saved_state_bytes_per_layer",
        help="bytes of carried states the backward of the latest traced "
             "ops.gdn.gdn keeps (one call = one layer); 0 = none traced"
    ).set(saved_state_bytes)
    registry().gauge(
        "horovod_gdn_key_lanes_padded",
        help="lanes a head's keys take where the latest traced ops.gdn.gdn "
             "runs (128 in the kernels, the head's own width in jax.numpy); "
             "0 = none traced"
    ).set(key_lanes)


_GDN_LAYERS = {}    # {a mixer's path in its model: its latest traced scan took the kernels}


def record_gdn_kernel_scan(layer: str, kernel: bool) -> None:
    """Record whether the scan of the ``models.gdn.GDNMixer`` at ``layer``
    (its path in the model, ``block_2/mixer``) took ``ops/gdn.py``'s pallas
    kernels when it was last traced (trace time; a layer is traced for every
    program that holds it, and again for its recomputation).
    ``horovod_gdn_kernel_scans`` is the number of layers whose latest traced
    scan did: a model of three such layers reads 3 while every one of them
    runs the kernels, forward and backward, in whatever was traced last."""
    _GDN_LAYERS[layer] = bool(kernel)
    registry().gauge(
        "horovod_gdn_kernel_scans",
        help="GDNMixer layers (by their path in the model) whose latest "
             "traced scan took the kernels hvd_gdn_scan_fwd / _bwd; 0 = none "
             "traced, or every one kept jax.numpy"
    ).set(sum(_GDN_LAYERS.values()))


def record_loop_plan(passes: int, block_applications: int) -> None:
    """Record the loop of the latest traced looped ``TransformerLM``
    (``passes`` > 1 or ``exit_gate``; trace time, once per compile): the
    passes the residual stream makes over the one stack, and the block
    applications that is (``layers x passes``: what the step runs, and under
    ``remat`` recomputes one by one). Both 0 until such a model is traced."""
    registry().gauge(
        "horovod_loop_passes",
        help="passes over the shared stack of layers in the latest traced "
             "looped TransformerLM; 0 = none traced"
    ).set(passes)
    registry().gauge(
        "horovod_loop_block_applications",
        help="block applications (layers x passes) of the latest traced "
             "looped TransformerLM; 0 = none traced"
    ).set(block_applications)


def record_loop_exit_mass(exit_mass) -> None:
    """Record where a looped model's tokens EXIT in the steps a training loop
    hands in: ``exit_mass`` is (steps, passes) of the mean ``p_t`` over each
    step's tokens (``models.transformer.loop_lm_loss``'s ``exit_mass``),
    CONCRETE (read on the host between steps, as ``record_moe_live_rows``'
    rows are; never from inside a jitted step). ``horovod_loop_exit_mass``,
    labelled by the pass (``loop_pass``, 1-based: ``pass`` is no keyword a
    call can give), is the mean over the steps; the passes'
    values sum to 1 and ``sum_t t x mass[t]`` is the mean exit pass: a gate
    that died reads 1.0 (everything leaves after the first pass) or the
    number of passes (nothing leaves early)."""
    steps = [[float(p) for p in step] for step in exit_mass]
    if not steps:
        return
    for t in range(len(steps[0])):
        registry().gauge(
            "horovod_loop_exit_mass",
            help="mean share of a step's tokens whose exit distribution "
                 "leaves after this pass of a looped model (mean over the "
                 "recorded steps; the passes sum to 1)",
            loop_pass=str(t + 1)
        ).set(sum(step[t] for step in steps) / len(steps))


def record_short_conv_plan(taps: int, kernel: bool) -> None:
    """Record the taps of the latest traced gated short convolution
    (``models.short_conv.ShortConvMixer``; trace time, once per compile, from
    the call's own shapes: ``horovod_kda_chunk_len``'s rule). 0 until such a
    mixer is traced. ``kernel``: whether its shapes took the pass's pallas
    kernels; ``horovod_short_conv_kernel_passes`` counts the traced passes
    that did since the latest one that kept ``jax.numpy``, which sets it back
    to 0 (``horovod_kda_kernel_scans``' rule)."""
    registry().gauge(
        "horovod_short_conv_taps",
        help="taps of the latest traced gated short convolution "
             "(models/short_conv.py); 0 = none traced"
    ).set(taps)
    passes = registry().gauge(
        "horovod_short_conv_kernel_passes",
        help="traced gated short convolutions whose shapes took the pass's "
             "kernels (hvd_sconv_conv_fwd / _bwd) since the latest one that "
             "kept jax.numpy; 0 = none traced, or the latest kept jax.numpy")
    if kernel:
        passes.inc()
    else:
        passes.set(0)


def record_attn_gate_width(width: int) -> None:
    """Record how many gate values a token the latest traced gated softmax
    attention (``models.transformer.Block.attn_gate``) multiplies its output
    by (trace time, once per compile): the layer's heads where the gate is
    one number a head, heads x head_dim where it is one an element. 0 until
    such a layer is traced."""
    registry().gauge(
        "horovod_attn_gate_width",
        help="sigmoid gate values a token on the output of the latest "
             "traced gated attention layer: heads (a gate a head) or heads "
             "x head_dim (a gate an element); 0 = none traced"
    ).set(width)


def record_mamba_fused_passes(passes: int) -> None:
    """Record how many of the latest traced ``models.mamba.Mamba2Mixer``'s two
    elementwise chains (convolution + silu, the gated norm) went through a
    kernel of ``ops.mamba_fused`` (trace time, once per compile): 2, 1, or 0
    where both shapes kept ``jax.numpy``. 0 until a mixer is traced."""
    registry().gauge(
        "horovod_mamba_fused_passes",
        help="chains of the latest traced Mamba2Mixer (convolution + silu, "
             "gated norm) that went through a fused kernel; 0 = none traced "
             "or both in jax.numpy"
    ).set(passes)


# Latest fabric-tier plan of the hierarchical compiled path (ISSUE 7):
# {"hierarchical": bool, "ici_wire": str, "dcn_wire": str, "ici_size": int,
#  "bytes_per_step": {"ici": n, "dcn": n}, "buckets": int}.
_last_tier_plan: Optional[dict] = None


def record_tier_plan(hierarchical: bool, ici_wire: str, dcn_wire: str,
                     ici_size: int, bucket_bytes: list,
                     dcn_bucket_bytes: list) -> dict:
    """Record the latest fused_allreduce call's per-fabric-tier plan
    (trace time, once per compile — same reasoning as record_wire_plan).

    ``bucket_bytes``: per-bucket bytes each device moves over ICI (the
    reduce-scatter/all-gather stages, at the ICI wire dtype);
    ``dcn_bucket_bytes``: per-bucket bytes each device moves over DCN (the
    cross-host psum carries 1/ici_size of the bucket, at the DCN wire
    dtype). For a flat plan the DCN list is empty and ``hierarchical`` is
    False — the gauges always say which ladder the trace compiled."""
    global _last_tier_plan
    reg = registry()
    plan = {"hierarchical": bool(hierarchical), "ici_wire": ici_wire,
            "dcn_wire": dcn_wire, "ici_size": int(ici_size),
            "buckets": len(bucket_bytes),
            "bytes_per_step": {"ici": int(sum(bucket_bytes)),
                               "dcn": int(sum(dcn_bucket_bytes))}}
    reg.gauge(
        "horovod_compiled_hierarchical",
        help="1 when the latest compiled plan rides the two-level "
             "(ici, dcn) ladder, 0 for the flat allreduce").set(
        1.0 if hierarchical else 0.0)
    for tier, total in plan["bytes_per_step"].items():
        reg.gauge(
            "horovod_compiled_tier_bytes_per_step",
            help="gradient bytes per step per device the latest compiled "
                 "plan moves over each fabric tier", tier=tier).set(total)
    reg.set_info("compiled_tier_plan", plan)
    _last_tier_plan = plan
    return plan


def last_tier_plan() -> Optional[dict]:
    """The most recent fused_allreduce trace's fabric-tier plan."""
    return _last_tier_plan


# Latest sharded (ZeRO) plan of the compiled path (ISSUEs 14/19):
# {"batch": int, "shard": int, "model": int, "buckets": int,
#  "scatter_bytes": [...], "gather_bytes": [...],
#  "bytes_per_step": {"scatter": n, "gather": n}}.
_last_shard_plan: Optional[dict] = None


def record_shard_plan(batch_size: int, shard_size: int,
                      scatter_bytes: list, gather_bytes: list,
                      model_size: int = 1) -> dict:
    """Record the latest sharded gradient exchange's plan (trace time, once
    per compile — same reasoning as record_wire_plan).

    ``scatter_bytes``: per-bucket bytes of the reduce-scatter operand (at
    the wire dtype — what each bucket's collective moves);
    ``gather_bytes``: per-bucket bytes of the parameter-refresh allgather
    (at the storage dtype). On a degenerate shard=1 mesh the gauges still
    record (scatter == the DP allreduce operand, gather == 0 collectives
    but the refresh bytes are reported for comparability).

    ``model_size`` is the third ('model') mesh axis (ISSUE 19): the byte
    lists are one model rank's exchange over its local slice tree, and
    the gauge is how the controller and dashboards see which 3-D shape
    the step compiled (1 = the 2-D plan)."""
    global _last_shard_plan
    reg = registry()
    plan = {"batch": int(batch_size), "shard": int(shard_size),
            "model": int(model_size),
            "buckets": len(scatter_bytes),
            "scatter_bytes": [int(n) for n in scatter_bytes],
            "gather_bytes": [int(n) for n in gather_bytes],
            "bytes_per_step": {"scatter": int(sum(scatter_bytes)),
                               "gather": int(sum(gather_bytes))}}
    for axis, size in (("batch", batch_size), ("shard", shard_size),
                       ("model", model_size)):
        reg.gauge(
            "horovod_compiled_shard_plan",
            help="axis sizes of the latest compiled sharded "
                 "(reduce-scatter/allgather) plan's "
                 "('batch','shard','model') mesh (model=1 = the 2-D plan)",
            axis=axis).set(int(size))
    for stage, total in plan["bytes_per_step"].items():
        reg.gauge(
            "horovod_compiled_shard_bytes_per_step",
            help="gradient-exchange bytes per step per device the latest "
                 "compiled sharded plan moves in each stage (scatter = "
                 "reduce-scatter operand at wire dtype, gather = parameter "
                 "refresh at storage dtype)", stage=stage).set(total)
    reg.set_info("compiled_shard_plan", plan)
    _last_shard_plan = plan
    return plan


def last_shard_plan() -> Optional[dict]:
    """The most recent sharded gradient exchange's plan."""
    return _last_shard_plan


def record_sharded_state_bytes(total_bytes: int, shard_size: int,
                               model_size: int = 1) -> float:
    """Publish the per-rank parameter+optimizer-state footprint of a sharded
    training state (the headline ISSUE 14 measurement: ~shard-fold smaller
    than DP's fully-replicated state). ``total_bytes`` is the global state
    size; each rank persists 1/(shard_size*model_size) of it — the model
    axis (ISSUE 19) slices the state again on top of the ZeRO partition."""
    per_rank = total_bytes / max(1, shard_size * model_size)
    registry().gauge(
        "horovod_sharded_state_bytes_per_rank",
        help="bytes of parameters + optimizer state each rank persists "
             "under the current sharded (ZeRO) layout; equals the full "
             "state size when shard=1 (plain DP)").set(per_rank)
    return per_rank


# ------------------------------------------------------- measured overlap


def overlap_report(collectives: list) -> dict:
    """The report of :func:`measure_overlap` from collective intervals
    ``[(device, name, start_ns, end_ns, hidden_ns)]`` (``device_profile.
    collective_overlap``): ``hidden_ns`` is the part of an interval covered by
    non-collective ops of the SAME device (a collective on chip A over compute
    on chip B is parallelism, not latency hiding)."""
    if not collectives:
        return {"ok": False,
                "reason": "no device collective spans in trace (a CPU "
                          "backend's trace carries host frames only; a world "
                          "of one chip runs no collective)"}
    total = sum(end - start for _, _, start, end, _ in collectives)
    hidden = sum(h for *_, h in collectives)
    spans = [{"name": name, "ms": (end - start) / 1e6, "hidden_ms": h / 1e6,
              "start_us": start / 1e3, "end_us": end / 1e3}
             for _, name, start, end, h in
             sorted(collectives, key=lambda c: c[2])]
    return {
        "ok": True,
        "collectives": len(collectives),
        "collective_ms": round(total / 1e6, 3),
        "hidden_ms": round(hidden / 1e6, 3),
        "overlap_efficiency": round(hidden / total, 4) if total else 0.0,
        "spans": spans[:64],
    }


def measure_overlap(run_step: Callable[[], None], steps: int = 3,
                    sync: Optional[Callable[[], None]] = None,
                    logdir: Optional[str] = None) -> dict:
    """Profile ``steps`` calls of a warmed ``run_step`` and publish the
    measured overlap-efficiency gauge. Returns the report; the device trace
    is read by ``metrics/device_profile.py`` from the profiler's
    ``.xplane.pb`` (collectives by HLO opcode)."""
    import jax

    from . import device_profile

    fence = sync or (lambda: None)
    logdir = logdir or tempfile.mkdtemp(prefix="hvd_overlap_")
    with jax.profiler.trace(logdir):
        for _ in range(steps):
            run_step()
        fence()
    try:
        rep = overlap_report(device_profile.collective_overlap(
            device_profile.load(device_profile.find_xplane(logdir))))
    except (FileNotFoundError, IndexError, ValueError) as e:
        rep = {"ok": False, "reason": f"trace unreadable: {e}"}
    rep["logdir"] = logdir
    if rep.get("ok"):
        reg = registry()
        reg.gauge("horovod_overlap_efficiency_measured",
                  help="fraction of compiled-path collective device time "
                       "hidden under concurrent compute (profiler-derived)"
                  ).set(rep["overlap_efficiency"])
        reg.gauge("horovod_overlap_collective_ms",
                  help="collective device ms in the profiled window"
                  ).set(rep["collective_ms"])
        reg.gauge("horovod_overlap_hidden_ms",
                  help="collective device ms overlapped with compute"
                  ).set(rep["hidden_ms"])
    return rep
