"""KV-cache incremental decoding + continuous-batching serving.

The "millions of users" half of the north star (ROADMAP item 1),
layered on the inference Predictor ABI:

- paging.py   Host-side paged-cache bookkeeping: PagePool (refcounted
              free-list allocator over [num_pages, page_tokens, H, dk]
              pools, typed retryable CacheExhaustedError when dry),
              PageTable (per-stream logical -> physical map with
              copy-on-write forks), PrefixCache (content-hash chain
              over full pages + partial tails — shared system prompts
              map their prefix pages read-only, zero recompute).
- paged.py    PagedDecodePredictor: a loaded LM transpiled into a
              paged prefill + decode program pair
              (transpiler/decode_transpiler.py) whose per-layer page
              pools live in a child Scope — weights shared with the
              base Predictor (and every clone) through the parent
              Scope, cache state private per worker. Chunked prefill
              (one FLAGS_serving_prefill_chunk slice per engine
              iteration), page index as a decode feed (no recompile
              per admission), transactional on-demand page allocation.
- speculative.py  SpeculativeDecodePredictor: draft/verify speculative
              decoding over the paged cache — a layer-truncated
              self-draft (or explicit draft LM) proposes FLAGS_spec_k
              tokens per stream, one batched verify pass scores all
              k+1 positions for every slot, and greedy acceptance
              (longest matching prefix + free bonus token) keeps the
              emitted stream token-for-token identical to plain greedy
              decode. Mid-verify pool exhaustion rolls the whole
              speculation back and retries as a plain decode step.
- engine.py   ServingEngine: continuous batching over a fixed slot
              pool — requests are admitted into the running batch
              between decode steps, finished/cancelled slots are
              evicted and their pages released, worker threads share weights via
              clone(). serving.* telemetry flows into paddle_tpu/obs/.
- preempt.py  Preempt-first capacity policy: SLO tiers
              (submit(priority=)), victim selection (lowest tier,
              longest idle), and a host-RAM swap budget
              (FLAGS_serving_swap_host_mb) — on pool exhaustion the
              engine swaps a low-tier stream's pages to host memory
              (or drops and re-prefills when the budget is dry) and
              resumes it bit-exactly once pressure clears.
- api.py      LMServer: the user-facing blocking generate() + async
              submit/poll surface (reference
              inference/api/paddle_inference_api.h PaddlePredictor
              serving contract, re-shaped for token streams).
- replica.py  ReplicaServer: one LMServer exposed on the wire (SRV_*
              message types) so a fleet router can address it.
- disagg.py   Disaggregated prefill/decode: KV pages as first-class
              wire objects (SRV_PAGES / SRV_PAGE_FETCH) — a prefill
              tier computes pages once per unique prefix and ships
              them content-addressed to decode replicas; every ship
              failure falls back to bit-exact local re-prefill.
- fleet.py    FleetRouter: health-checked dispatch over N replicas
              with session affinity, transparent mid-stream failover
              (greedy re-prefill from the accumulated prefix),
              SLO-rule admission control (typed OverloadError), and
              zero-drop rolling weight deploys; FleetAutoscaler drives
              replica count from the same signals.

Decode cost per token is O(1) against the cache instead of O(T) prefix
recompute, and greedy decode is bit-exact against the full-recompute
path (tests/test_serving.py); the same determinism makes fleet
failover bit-exact (tests/test_fleet.py).
"""
from .paging import (CacheExhaustedError, PagePool, PageTable,
                     PrefixCache, chain_keys)
from .paged import PagedDecodePredictor
from .speculative import DraftModel, SpeculativeDecodePredictor
from .engine import ServingEngine, Request, DeadlineExceededError
from .preempt import HostSwapBudget
from .api import LMServer
from .replica import ReplicaServer
from .disagg import ShipError
from .fleet import (FleetRouter, FleetAutoscaler, FleetRequest,
                    OverloadError, FleetDeployError)

__all__ = ['PagedDecodePredictor', 'DraftModel',
           'SpeculativeDecodePredictor',
           'CacheExhaustedError', 'PagePool', 'PageTable', 'PrefixCache',
           'chain_keys', 'ShipError',
           'ServingEngine', 'Request', 'DeadlineExceededError',
           'HostSwapBudget', 'LMServer',
           'ReplicaServer', 'FleetRouter', 'FleetAutoscaler',
           'FleetRequest', 'OverloadError', 'FleetDeployError']
