"""paddle_tpu.serving — continuous-batching online inference.

Wraps the compiled decode path (nlp/generation.py) in a slot-based
scheduler over a PAGED KV pool: requests arriving at different times,
with different prompt lengths and sampling params, share ONE compiled
unified ragged prefill+decode step — decode rows next to mid-prefill
rows at q_len up to chunk_len in the same fixed-shape invocation,
prefill tokens packed into spare decode capacity — each holding only
the KV pages its prompt + output budget needs. A decode row is no
longer pinned to one token per step:
with SPECULATIVE DECODING on (PADDLE_TPU_SPEC_DECODE=ngram[:k] or
model[:k] / ServingEngine(spec=...), serving/spec.py + serving/
draft.py, default off) a per-request drafter — model-free n-gram
lookup, or a small RESIDENT DRAFT MODEL decoding through its own
paged KV pool — proposes up to k next tokens, the row verifies them
at q_len 1+k through the SAME step, and the whole accepted burst is
emitted at once — still bit-token-identical to one-at-a-time greedy
decode:

    from paddle_tpu.serving import ServingEngine, SamplingParams

    eng = ServingEngine(model, num_slots=8, max_len=256,
                        page_size=16, chunk_len=32)
    req = eng.add_request(prompt_ids,
                          SamplingParams(max_new_tokens=32,
                                         eos_token_id=eos))
    while eng.has_work:
        for out in eng.step():
            print(out.request_id, out.token_ids, out.finish_reason)
    print(eng.metrics.snapshot()["pool"])

The paged pools can run QUANTIZED (PADDLE_TPU_KV_DTYPE=fp|int8|fp8 /
ServingEngine(kv_dtype=...), default fp): int8 code pages + per-page
rowwise scale pages hold ~2x the resident tokens per HBM byte, the
ragged kernel dequantizes in-VMEM (fused into the softmax loop), and
every whole-page move — prefix COW, preemption swap, host spill —
carries codes and scales together, so int8 serving stays
deterministic and feature-on/off token-identical (fp drift bounded,
benched via serving_bench --quant-ab). fp8 is the pure-convert
f8_e4m3 lane: no scale pages at all, one byte per element, pages
move like fp pages (drift pinned in tests/test_serving_fp8.py).

Attention is PREFIX-SHARING-AWARE (wherever the Pallas walk serves an
engine that has a prefix cache; no option): rows whose page tables
share a physical-page prefix — the radix cache attached the same
pages — are grouped host-side each step and the kernel streams each
shared page from HBM once per GROUP instead of once per row, outputs
bit-identical to the per-row walk.

One replica can span a MULTI-CHIP MESH (serving/tp.py, default off,
PADDLE_TPU_MESH=dpXmpY / ServingEngine(mesh=...)): the per-layer KV
pools shard over their kv-head axis and the QKV projections over
whole heads across the mesh's mp degree — mp x the residents per
chip-HBM byte — while page tables, scheduler, prefix cache,
preemption and spec decode stay replicated and unchanged, the step
stays ONE compiled program, and the only collective is a single
bit-exact output all-gather per layer (mp>1 is bit-token-identical
to the mp=1 oracle; serving_bench --tp-ab pins the collective count
and the residents-per-chip win).

One fleet can serve MANY TENANTS (serving/adapters.py, default off,
PADDLE_TPU_ADAPTERS=on / ServingEngine(adapters=...)): registered
LoRA fine-tunes (per-layer A/B pairs, rank-bucketed) live in a paged
ADAPTER pool under the same PagePool refcount/park/evict/spill
discipline as the KV pages, per-slot adapter ids ride the unified
step as operand data, and each row's low-rank delta fuses into the
q/k/v/o projections in-trace — a batch mixing N tenants plus
base-model rows is still the ONE compiled program, and each tenant's
stream is bit-token-identical to a solo dense-merged (W + B·A)
engine. HTTP picks tenants via the OpenAI-style `model=` field; the
prefix cache is tenant-namespaced; the router places by adapter
affinity.

OVERLOAD degrades gracefully instead of refusing (default on,
PADDLE_TPU_PREEMPT / ServingEngine(preempt=...)): requests carry
`priority` + placement `deadline_s`, the queue orders by (priority,
deadline, arrival), a blocked higher-priority request preempts the
least-important resident (tokens banked, KV swapped whole-page to the
host-RAM tier, resumed later token-identically), and queued requests
past their deadline fail fast as typed DeadlineExceeded (HTTP 504).

The fleet is OBSERVABLE as one system (serving/obs.py +
serving/slo.py, default on): request-lifecycle timelines + a
per-step flight recorder, a burn-rate SLO tracker (TTFT p99 /
inter-token p99 / deadline goodput over fast+slow sliding windows,
per priority class and per tenant, ok|warn|page states exported as
Prometheus gauges and noted into the flight ring), a once-per-compile
cost census of the ONE unified step (PADDLE_TPU_COST_CENSUS) with
per-step `achieved_util`, and a router-level fleet view
(`GET /debug/fleet`, `scripts/fleet_top.py`). All host-side work —
`serving_bench --obs-ab` pins it on/off token-identical within 3%.

The fleet STEERS ITSELF from those signals (serving/controlplane.py,
default off, PADDLE_TPU_CONTROLPLANE=on / Router(controller=...) /
serve(controller=...)): a pure host-side FleetController turns the
PR-15 telemetry into three actuators — SLO-aware placement (the
router ranks warn-state replicas below ok and page below warn, after
the breaker, before load), deadline-aware admission (a request whose
deadline is infeasible given queue depth x census-predicted step cost
is shed AT THE DOOR with 429 + Retry-After, type
`deadline_infeasible`, instead of timing out after burning pages),
and reactive burn-rate autoscaling (double-window burn => scale up,
sustained idle => drain one surplus replica gracefully, with
hysteresis + per-direction cool-downs; `Router.add_replica` /
`remove_replica` grow and shrink the live fleet). Zero compiled-
program changes — controller on/off is bit-token-identical at fixed
fleet size; `serving_bench --autoscale-ab` drives a diurnal trace
where reactive scaling holds TTFT p99 within SLO at roughly half the
fixed fleet's replica-seconds.

N replicas behave as ONE LOGICAL KV CACHE (serving/fabric.py,
default off, PADDLE_TPU_KV_FABRIC=on / Router(fabric=...)): committed
prefix pages serialize into a versioned transfer frame (int8 ships
codes+scales at ~half the f32 wire bytes, fp8 a quarter) and graft
into another replica's radix tree, so role-configured fleets run
DISAGGREGATED — long prompts prefill on prefill specialists at a
1-token budget, pages transfer, decode specialists continue the
stream token-identically; `RadixPrefixCache.snapshot()/load()` move
the whole tree (host tier included) across engine restarts so
rolling deploys start warm with zero re-prefill; and placement ranks
longest-prefix-affinity against per-replica fingerprint summaries
(refreshed on the controller poll) after breaker/SLO rank and before
load. All host-side: fabric off is bit-token-identical, fabric on is
token-identical to cold recompute (pages are exact quantized codes);
`serving_bench --disagg-ab` pins TTFT p99 + inter-token p99
improving together plus the restart-warmth win.

Greedy requests are bit-identical to offline CompiledGenerator decode
(tested); `scripts/serving_bench.py` drives a Poisson arrival trace and
reports TTFT/throughput/pool utilization into BENCH_serving.json
(every run also appends its headline tokens/s to BENCH_history.jsonl).
"""
from .adapters import (AdapterStore, LoRAWeights,  # noqa: F401
                       make_random_lora, resolve_adapters_flag,
                       BASE_ADAPTER)
from .controlplane import (ControlPlaneConfig, Decision,  # noqa: F401
                           DeadlineInfeasible, FleetController,
                           FleetSignals, parse_controlplane_spec,
                           resolve_controlplane, slo_placement_rank)
from .engine import (ServingEngine, resolve_kv_dtype,  # noqa: F401
                     resolve_preempt_flag)
from .tp import (ServingTP, collective_counts,  # noqa: F401
                 parse_mesh_spec, resolve_serving_mesh)
from .errors import (DeadlineExceeded, EngineClosed,  # noqa: F401
                     PoisonedRequest, QueueFull, RateLimited,
                     ServingError)
from .fabric import (FabricConfig, decode_frame,  # noqa: F401
                     encode_frame, frame_header, parse_fabric_spec,
                     prompt_fingerprints, resolve_fabric)
from .faults import (FaultInjector, InjectedFault,  # noqa: F401
                     resolve_faults)
from .grammar import (ChoiceGrammar, GrammarSpec,  # noqa: F401
                      JsonGrammar, RegexGrammar, TokenGrammar,
                      resolve_grammar_flag)
from .metrics import (Histogram, ServingMetrics,  # noqa: F401
                      prometheus_render)
from .obs import (EngineObs, FlightRecorder,  # noqa: F401
                  RequestTracer, resolve_debug_flag,
                  resolve_flight_steps, resolve_obs_flag,
                  timeline_to_chrome)
from .paging import HostPagePool, PagePool, pages_needed  # noqa: F401
from .prefix import (PrefixGrant, RadixPrefixCache,  # noqa: F401
                     resolve_prefix_cache_flag, shared_prefix_groups)
from .request import (Request, RequestOutput, RequestState,  # noqa: F401
                      SamplingParams)
from .scheduler import Scheduler  # noqa: F401
from .slo import (SLOConfig, SLOTracker,  # noqa: F401
                  model_cost_census, resolve_cost_census,
                  resolve_slo_config)
from .spec import (Drafter, ModelDrafter, NgramDrafter,  # noqa: F401
                   SpecConfig, resolve_spec_config)
from .draft import (DraftConfig, DraftEngine,  # noqa: F401
                    make_draft_model)

__all__ = ["AdapterStore", "LoRAWeights", "make_random_lora",
           "resolve_adapters_flag", "BASE_ADAPTER",
           "ServingEngine",
           "resolve_preempt_flag", "resolve_kv_dtype",
           "shared_prefix_groups", "Scheduler",
           "ServingMetrics", "Histogram",
           "prometheus_render", "PagePool", "HostPagePool",
           "pages_needed", "RadixPrefixCache", "PrefixGrant",
           "resolve_prefix_cache_flag", "Request", "RequestOutput",
           "RequestState", "SamplingParams", "ServingError",
           "QueueFull", "EngineClosed", "RateLimited",
           "PoisonedRequest", "DeadlineExceeded", "FaultInjector",
           "InjectedFault", "resolve_faults", "Drafter",
           "NgramDrafter", "ModelDrafter", "SpecConfig",
           "resolve_spec_config", "DraftConfig", "DraftEngine",
           "make_draft_model",
           "EngineObs", "FlightRecorder", "RequestTracer",
           "resolve_obs_flag", "resolve_debug_flag",
           "resolve_flight_steps", "timeline_to_chrome",
           "ServingTP", "resolve_serving_mesh", "parse_mesh_spec",
           "collective_counts", "SLOConfig", "SLOTracker",
           "resolve_slo_config", "resolve_cost_census",
           "model_cost_census", "ControlPlaneConfig", "Decision",
           "DeadlineInfeasible", "FleetController", "FleetSignals",
           "parse_controlplane_spec", "resolve_controlplane",
           "slo_placement_rank", "FabricConfig", "resolve_fabric",
           "parse_fabric_spec", "encode_frame", "decode_frame",
           "frame_header", "prompt_fingerprints",
           "TokenGrammar", "JsonGrammar", "ChoiceGrammar",
           "RegexGrammar", "GrammarSpec", "resolve_grammar_flag"]
