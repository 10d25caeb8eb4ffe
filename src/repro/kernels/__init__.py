"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package has:
  <name>.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (handles padding/reshapes, interpret fallback)
  ref.py    — pure-jnp oracle used by tests and as the CPU execution path

Kernels:
  delay_comp      — fused CoCoDC Algorithm-1 update (the paper's per-sync hot-spot)
  flash_attention — blockwise causal/sliding-window GQA attention
  rglru_scan      — chunked RG-LRU linear recurrence (Griffin/RecurrentGemma)
  rwkv6_scan      — chunked RWKV-6 WKV recurrence (matrix-valued head state)
  rms_norm        — fused RMSNorm (one HBM pass)
  flash_decode    — one-token GQA attention over ring-buffer KV caches (serving)
  delta_codec     — fused per-block absmax int8/int4 quantize+pack and
                    dequantize+unpack for the WAN delta wire format
  outer_update    — fused outer Nesterov step + fused delivery (Eq. 3 blend /
                    Algorithm-1 compensation + offline masking) over the flat
                    fragment plane — one dispatch per protocol transition
"""


def is_cpu() -> bool:
    """True when the default JAX backend is CPU — every kernel wrapper uses
    this single probe to pick interpret mode (and the big-array oracle
    shortcut) instead of re-implementing its own backend check."""
    import jax
    return jax.default_backend() == "cpu"


def stream_kernel_specs() -> "list[dict]":
    """Analytic per-element cost model of every PROTOCOL STREAM kernel — the
    single-pass HBM streams the engine dispatches per transition (delta wire
    codec, fused outer update/delivery). benchmarks/roofline.py and
    benchmarks/kernels.py iterate THIS list instead of hardcoding entries, so
    a new stream kernel lands on the roofline by registering here.

    Each entry: kernel name, flops_per_elem, bytes_per_elem (HBM read+write
    per processed element, f32 operands unless stated). All entries sit far
    left of the v5e ridge (~241 flop/B) — these kernels are bandwidth, not
    compute."""
    from repro.kernels.delta_codec.ops import CODEC_BITS
    specs = []
    for codec, bits in sorted(CODEC_BITS.items()):
        block = 256
        # ~3 flops/elem: absmax-reduce share, scale multiply, round/clip
        specs.append({"kernel": f"delta_codec_{codec}_encode",
                      "flops_per_elem": 3.0,
                      "bytes_per_elem": 4 + bits / 8 + 4 / block})
        specs.append({"kernel": f"delta_codec_{codec}_decode",
                      "flops_per_elem": 3.0,
                      "bytes_per_elem": bits / 8 + 4 / block + 4})
    # outer_update/nesterov: read theta+momentum+delta, write theta'+momentum'
    # (4 flops: mu*m, +d, d+mu*m_new -> *lr, +theta ~ 5 mul/add)
    specs.append({"kernel": "outer_update_nesterov",
                  "flops_per_elem": 5.0,
                  "bytes_per_elem": 3 * 4 + 2 * 4})
    # outer_update/deliver, per worker-stacked element: blend reads local +
    # the broadcast global fragment, writes local' (3 flops + select);
    # compensate additionally streams the initiation snapshot (~8 flops:
    # 2 sub, 2 div-as-stream, 3 mul, 2 add, select)
    specs.append({"kernel": "outer_update_deliver_blend",
                  "flops_per_elem": 4.0,
                  "bytes_per_elem": 2 * 4 + 4})
    specs.append({"kernel": "outer_update_deliver_compensate",
                  "flops_per_elem": 9.0,
                  "bytes_per_elem": 3 * 4 + 4})
    return specs
