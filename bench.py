"""Benchmark: ResNet-50 image featurization throughput (the north-star path).

Measures the flagship DNNModel/ImageFeaturizer inference path on whatever
accelerator is available (one real TPU chip under the driver). Numbers:

  - **steady_state** (the headline `value`): jitted bf16 ResNet-50 forward to
    the pooled-feature tap, inputs device-resident, with the repeat loop ON
    DEVICE (lax.fori_loop, min-of-3) — what the chip sustains when the input
    pipeline keeps up. CAUTION for future edits: the loop's iteration
    dependency must ride FLOAT arithmetic (`acc * 0.0`); an integer-cast
    dependency gets constant-folded and XLA hoists the forward out of the
    loop, inflating the number ~5x (observed; MFU > 1 was the tell).
  - **per_call_images_per_sec**: the same forward timed one executable call
    per batch from the host. Async dispatch pipelines the calls, so it
    should agree with steady_state, which cross-validates both.
  - **e2e**: each iteration ships a fresh uint8 batch host->device inside the
    timed region — the realistic pipeline boundary. The headline
    `e2e_images_per_sec` drives the framework's TransferRing
    (parallel/ingest.py — uint8 wire, H2D on the prefetch thread overlapping
    compute, N slots in flight) and ships the per-stage ingest decomposition
    (`ingest`: queue/h2d/compute/readback per batch, bytes, overlap ratio);
    `e2e_serial_images_per_sec` is the unpipelined device_put-per-call loop
    for comparison, and `wire_bytes_per_batch` vs
    `wire_bytes_per_batch_float32` records the 4x uint8-wire saving.
    Decode/resize are benchmarked separately (tools/). `h2d_gbps` is printed
    with it.
  - **paced_overlap**: a synthetic producer paced AT the compute time feeds
    the framework's DevicePrefetcher (the DataFrame->DNNModel input path) —
    `paced_overlap_ratio` is wall per batch over the serial bound
    (produce + compute): 1.0 = no overlap, 0.5 = perfect. Reported as the
    MIN of 3 repeats with the per-rep array and a sleep-fidelity probe
    alongside: a host under external load oversleeps, which only inflates
    the ratio.

Also prints `mfu`: achieved FLOP/s (steady-state) over the chip's peak bf16
FLOP/s, with the FLOP count taken from XLA's own cost analysis of the
compiled executable (not a hand-count).

Batch size 2048 is the measured optimum on TPU v5e.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...} —
baseline = 2000 images/sec/chip (BASELINE.md north star).
"""

from __future__ import annotations

import json
import time

import numpy as np

BASELINE_IMAGES_PER_SEC = 2000.0


def _peak_flops(device) -> float | None:
    """bf16 peak of this chip from the one table (obs/perf.PEAKS); an
    unlisted device reports mfu=None rather than a made-up denominator."""
    from mmlspark_tpu.obs.perf import peaks_for_kind

    row = peaks_for_kind(device.device_kind)
    return row["flops"] if row else None


def main() -> None:
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.models.module import FunctionModel
    from mmlspark_tpu.models.resnet import resnet

    dev = jax.devices()[0]
    on_accel = dev.platform != "cpu"
    batch = 2048 if on_accel else 16
    size = 224
    warmup = 3
    iters = 12 if on_accel else 3

    model = resnet(50, num_classes=1000, image_size=size)

    def fwd(params, x):
        # uint8 -> f32 on device (pixels ride the host link as uint8: 4x less traffic)
        live = FunctionModel(model.module, params, model.input_shape,
                             model.layer_names, model.name)
        feats = live.apply(x.astype(np.float32), tap="avgpool")
        return jnp.sum(feats)  # scalar witness: forces real execution on fetch

    featurize = jax.jit(fwd)

    params = jax.device_put(model.params)
    rng = np.random.default_rng(0)

    # ---- steady-state: device-resident input, repeat loop ON DEVICE ------
    batches = [jax.device_put(rng.integers(0, 256, size=(batch, size, size, 3),
                                           dtype=np.uint8)) for _ in range(2)]
    inner = 8 if on_accel else 2

    @jax.jit
    def fwd_loop(params, x):
        def body(i, acc):
            # the iteration dependency must ride FLOAT arithmetic: float
            # `acc * 0` is NaN/inf-preserving so XLA cannot fold it and hoist
            # the forward out of the loop (an integer-cast dependency DOES
            # fold — it silently turned this loop into one forward)
            live = FunctionModel(model.module, params, model.input_shape,
                                 model.layer_names, model.name)
            xf = x.astype(np.float32) + acc * 0.0
            return acc + jnp.sum(live.apply(xf, tap="avgpool"))
        return jax.lax.fori_loop(0, inner, body, jnp.float32(0))

    loop_c = fwd_loop.lower(params, batches[0]).compile()
    float(loop_c(params, batches[0]))  # warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        assert np.isfinite(float(loop_c(params, batches[0])))
        best = min(best, (time.perf_counter() - t0) / inner)
    steady_ips = batch / best

    # ---- per-call: one executable invocation per batch from the host -----
    # AOT-compile once and call the executable directly: the jitted wrapper
    # would not reuse this compilation, and a second multi-10s ResNet-50/2048
    # compile is real startup cost
    compiled = featurize.lower(params, batches[0]).compile()
    featurize = lambda p, x: compiled(p, x)  # noqa: E731
    flops_per_call = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops_per_call = float(ca.get("flops")) if ca.get("flops") else None
    except Exception:
        pass

    for i in range(warmup):
        float(featurize(params, batches[i % 2]))

    t0 = time.perf_counter()
    outs = [featurize(params, batches[i % 2]) for i in range(iters)]
    for o in outs:
        assert np.isfinite(float(o))
    dt = time.perf_counter() - t0
    per_call_ips = batch * iters / dt

    # ---- e2e: fresh uint8 batch host->device every step ------------------
    host_batches = [rng.integers(0, 256, size=(batch, size, size, 3),
                                 dtype=np.uint8) for _ in range(3)]
    float(featurize(params, jax.device_put(host_batches[0])))  # warm path
    e2e_iters = max(iters // 2, 2)
    t0 = time.perf_counter()
    outs = [featurize(params, jax.device_put(host_batches[i % 3]))
            for i in range(e2e_iters)]
    for o in outs:
        assert np.isfinite(float(o))
    e2e_dt = time.perf_counter() - t0
    e2e_serial_ips = batch * e2e_iters / e2e_dt

    # raw host->device bandwidth, so the e2e number is interpretable
    t0 = time.perf_counter()
    jax.device_put(host_batches[1]).block_until_ready()
    h2d_gbps = host_batches[1].nbytes / (time.perf_counter() - t0) / 1e9

    # ---- e2e through the ingest ring (the framework's data plane) --------
    # The production path (DNNModel.transform / ImageFeaturizer): pixels
    # ride the link uint8 (4x fewer bytes than the old host-side float32
    # preprocess), H2D runs on the ring's prefetch thread overlapping the
    # previous batch's compute, and every stage is timed per batch. The
    # headline e2e_images_per_sec is THIS number — the per-stage ingest
    # decomposition ships alongside so the e2e-vs-per-call gap is a
    # measured quantity, not a bench artifact.
    from mmlspark_tpu.parallel.ingest import IngestStats, TransferRing

    ring_iters = max(e2e_iters, 4)
    ring_stats = IngestStats()
    ring = TransferRing(
        (host_batches[i % 3] for i in range(ring_iters)),
        put=jax.device_put,
        step=lambda x: featurize(params, x),
        fetch=float,
        depth=3, stats=ring_stats)
    t0 = time.perf_counter()
    for o in ring:
        assert np.isfinite(o)
    ring_dt = time.perf_counter() - t0
    e2e_ips = batch * ring_iters / ring_dt

    wire_bytes_u8 = int(host_batches[0].nbytes)     # uint8 wire (default)
    wire_bytes_f32 = wire_bytes_u8 * 4              # legacy host-f32 wire

    # ---- input-pipeline overlap, synthetically paced ---------------------
    # Pace a synthetic producer at the measured per-batch compute time
    # (what a colocated decode pipeline would cost) and drive the
    # DataFrame->DNNModel prefetcher (parallel/batching.DevicePrefetcher).
    # Overlap active => wall time ~ max(produce, compute) per batch, vs the
    # serial bound produce + compute. (Round-2 verdict item 7; reference
    # analogue: background-thread DynamicBufferedBatcher,
    # stages/Batchers.scala:12-160.)
    from mmlspark_tpu.parallel.batching import DevicePrefetcher

    pace = best  # producer paced AT the compute time: hardest overlap case
    k_demo = 16 if on_accel else 2

    def paced_producer():
        for i in range(k_demo):
            time.sleep(pace)           # simulated decode + colocated H2D
            yield batches[i % 2]       # device-resident, link excluded

    # Repeat the paced run and take the BEST ratio: a host under external
    # load oversleeps, which only INFLATES the ratio, so min-of-N measures
    # the framework and the per-rep array + sleep-fidelity field expose an
    # environmental stall in the artifact instead of corrupting the
    # headline.
    serial_bound = pace + best
    paced_ratios = []
    oversleeps = []
    for _rep in range(3 if on_accel else 1):
        s0 = time.perf_counter()
        time.sleep(pace)               # sleep fidelity probe, same duration
        oversleeps.append((time.perf_counter() - s0) / pace - 1.0)
        t0 = time.perf_counter()
        outs = [featurize(params, x)
                for x in DevicePrefetcher(paced_producer())]
        # ONE sync for the whole chain: per-output fetches each block and
        # would masquerade as overlap loss
        total = outs[0]
        for o in outs[1:]:
            total = total + o
        assert np.isfinite(float(total))
        paced_ratios.append(((time.perf_counter() - t0) / k_demo)
                            / serial_bound)
    overlap_ratio = min(paced_ratios)  # ~0.5 = perfect overlap
    t_overlap = overlap_ratio * serial_bound

    # Measure the residual DIRECTLY (round-3 verdict item 6): the host-side
    # cost of one dispatch = wall time of the featurize() CALL (it returns
    # at enqueue, before execution). A single consumer thread cannot hide
    # this — it is serial host work between batches — so the paced floor is
    # (pace + dispatch) / (2 * pace). Emitted alongside the measured ratio
    # so the artifact shows floor ~= measured (dispatch-bound, not GIL).
    # (device idle here: the float(total) above synced the paced chain)
    d_times = []
    last = None
    for i in range(6):
        c0 = time.perf_counter()
        last = featurize(params, batches[i % 2])
        d_times.append(time.perf_counter() - c0)
    assert np.isfinite(float(last))
    dispatch_host_s = min(d_times)  # min: enqueue cost, not backpressure
    # What remains at the knife edge (pace == compute) is the finite-k
    # pipeline-fill bound below plus sleep jitter.
    pipeline_fill_floor = (k_demo + 2) / (2.0 * k_demo)
    predicted_floor = max(
        (pace + dispatch_host_s) / serial_bound, pipeline_fill_floor)

    # ---- pipeline fusion: fused vs unfused Transformer chain -------------
    # The e2e sections above measure ONE stage's ingest; real pipelines
    # chain stages, and unfused every boundary pays a per-row host pass, a
    # host re-batch, and (on accelerators) a fresh upload of the
    # intermediate. The fused plan (core/fusion.py) compiles the
    # ImageTransformer ops + featurizer forward into ONE XLA program per
    # shape bucket: raw uint8 on the wire, one dispatch, one readback, no
    # host materialization of the intermediate image columns. The backbone
    # here is deliberately SMALL so the section measures the stage-BOUNDARY
    # tax rather than re-measuring big-model compute (Amdahl: a heavy
    # forward amortizes any boundary; the resnet50 numbers live above).
    from mmlspark_tpu.core.dataframe import DataFrame
    from mmlspark_tpu.core.device_stage import compile_cache
    from mmlspark_tpu.core.pipeline import PipelineModel
    from mmlspark_tpu.core.schema import ImageSchema
    from mmlspark_tpu.image.featurizer import ImageFeaturizer
    from mmlspark_tpu.image.stages import ImageTransformer
    from mmlspark_tpu.models.module import (BatchNorm, Conv2D, FunctionModel,
                                            GlobalAvgPool, Sequential, relu)

    n_img = 4096 if on_accel else 2048
    fsize = 64 if on_accel else 16
    fbatch = 512 if on_accel else 256
    fmod = Sequential([("conv", Conv2D(16 if on_accel else 4, (3, 3))),
                       ("bn", BatchNorm()), ("act", relu()),
                       ("pool", GlobalAvgPool())], name="fuse_bench")
    fparams, _ = fmod.init(jax.random.PRNGKey(7), (fsize, fsize, 3))
    fmodel = FunctionModel(fmod, fparams, (fsize, fsize, 3),
                           layer_names=["pool", "act"], name="fuse_bench")
    imgs = np.empty(n_img, dtype=object)
    for k in range(n_img):
        imgs[k] = ImageSchema.make(
            rng.integers(0, 256, (fsize, fsize, 3), dtype=np.uint8),
            f"bench{k}")
    fdf = DataFrame.from_dict({"image": imgs})
    feat_stage = ImageFeaturizer(scaleFactor=1 / 255., batchSize=fbatch,
                                 cutOutputLayers=1).set_model(fmodel)
    chain = PipelineModel([
        ImageTransformer().flip(1).threshold(100.0, 255.0),
        ImageTransformer().flip(0).color_format("bgr2rgb"),
        ImageTransformer().crop(0, 0, fsize, fsize).flip(1), feat_stage])

    fused_chain = chain.fuse()
    chain.transform(fdf)        # warm the unfused per-stage jits
    fused_chain.transform(fdf)  # warm: compiles the fused executables
    cc0 = compile_cache().stats()
    # alternate reps and take each side's best: the two paths see the same
    # noise (shared single-core hosts stall unpredictably), so min-of-N per
    # side measures the framework, not the neighbors
    unfused_s = fused_s = float("inf")
    for _ in range(5 if not on_accel else 3):
        t0 = time.perf_counter()
        chain.transform(fdf)
        unfused_s = min(unfused_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        fused_chain.transform(fdf)
        fused_s = min(fused_s, time.perf_counter() - t0)
    h2d_unfused = (feat_stage.last_ingest_stats.summary().get("bytes", 0)
                   if feat_stage.last_ingest_stats else 0)
    cc1 = compile_cache().stats()
    warm_calls = (cc1["hits"] - cc0["hits"]) + (cc1["misses"] - cc0["misses"])
    warm_hit_rate = ((cc1["hits"] - cc0["hits"]) / warm_calls
                     if warm_calls else None)
    fstats = fused_chain.fusion_stats()
    h2d_fused = sum(s.get("bytes", 0) for s in fstats["per_segment"].values())
    fusion_section = {
        "fused_images_per_sec": round(n_img / fused_s, 1),
        "unfused_images_per_sec": round(n_img / unfused_s, 1),
        "fused_over_unfused": round(unfused_s / fused_s, 3),
        "h2d_bytes_unfused": int(h2d_unfused),
        "h2d_bytes_fused": int(h2d_fused),
        # the first two transformers' output columns (f64 after threshold):
        # unfused materializes n image structs on host at EACH boundary and
        # re-batches them; fused overwrites them in-program and never reads
        # them back (only the final image column + features return)
        "intermediate_host_bytes_eliminated": int(
            2 * n_img * fsize * fsize * 3 * 8),
        "segments": fstats["segments"],
        "fallbacks": fstats["fallbacks"],
        "compile_cache": cc1,
        "compile_cache_hit_rate_after_warmup": (round(warm_hit_rate, 4)
                                                if warm_hit_rate is not None
                                                else None),
        "per_segment_ingest": fstats["per_segment"],
    }

    peak = _peak_flops(dev)
    mfu = (round(steady_ips / batch * flops_per_call / peak, 3)
           if (flops_per_call and peak) else None)

    print(json.dumps({
        "metric": "resnet50_featurize_images_per_sec_per_chip",
        "value": round(steady_ips, 1),
        "unit": "images/sec",
        "vs_baseline": round(steady_ips / BASELINE_IMAGES_PER_SEC, 3),
        "per_call_images_per_sec": round(per_call_ips, 1),
        "e2e_images_per_sec": round(e2e_ips, 1),
        "e2e_serial_images_per_sec": round(e2e_serial_ips, 1),
        "wire_bytes_per_batch": wire_bytes_u8,
        "wire_bytes_per_batch_float32": wire_bytes_f32,
        "wire_bytes_ratio": round(wire_bytes_u8 / wire_bytes_f32, 3),
        "wire_dtype": "uint8",
        "ingest": ring_stats.summary(),
        "h2d_gbps": round(h2d_gbps, 3),
        "paced_overlap_images_per_sec": round(batch / t_overlap, 1),
        "paced_overlap_ratio": round(overlap_ratio, 3),
        "paced_overlap_ratio_reps": [round(r, 3) for r in paced_ratios],
        "sleep_oversleep_frac": round(max(oversleeps), 3),
        "dispatch_host_ms_per_call": round(dispatch_host_s * 1e3, 1),
        "paced_overlap_predicted_floor": round(predicted_floor, 3),
        "pipeline_fill_floor_k": round(pipeline_fill_floor, 3),
        "pipeline_fusion": fusion_section,
        "batch": batch,
        "mfu": mfu,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }))


if __name__ == "__main__":
    main()
