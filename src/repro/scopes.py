"""The names the program gives its own work in a profiler trace.

Device scopes are ``jax.named_scope`` s inside the compiled steps: each
becomes a component of its operations' op path
(``jit(decode_step)/.../attention/kv_gather/...``); a fusion carries the
path of its root.  The weight-switch kernels are named by their
``pallas_call``'s ``name=``.  Host spans are ``jax.profiler.
TraceAnnotation`` s around the phases of ``DecodeServer.tick``.  Scopes
are compile-time metadata only, and a span records nothing unless a
profiler is running.  Code that reads a trace imports these names rather
than spelling them.
"""

# device scopes, inside the decode and prefill-chunk steps
KV_WRITE = "kv_write"          # the step's K/V into the layer's pages, and
                               # the layer's pool back into the stack (a
                               # decode step that reads its pages in
                               # place: the scatter into the stack alone)
KV_GATHER = "kv_gather"        # the layer's pool out of the stack, and its
                               # pages gathered into a per-slot view (none
                               # in a decode step that reads in place)
ATTENTION = "attention"        # q/k/v, rope, scores, softmax, wo (the KV
                               # scopes nest inside it)
MCMA_PLAN = "mcma_plan"        # tick router, route, class sort, capacities
EXACT_FFN = "exact_ffn"        # the exact leg of a dispatch
UNEMBED = "unembed"
SSM_MIXER = "ssm_mixer"        # a Mamba2 mixer: projections, conv, SSD,
                               # gated norm (SSM_STATE nests inside it)
SSM_STATE = "ssm_state"        # what reads or writes the mixer's state:
                               # the conv-state shift, the recurrent state
                               # update and its read-out C.h, and the
                               # layer's state out of and back into the
                               # decode cache's stack
DEVICE_SCOPES = (KV_WRITE, KV_GATHER, ATTENTION, MCMA_PLAN, EXACT_FFN,
                 UNEMBED, SSM_MIXER, SSM_STATE)

# the weight-switch kernels' pallas_call names
SWITCH_KERNEL = "mcma_switched_mlp"
SWITCH_KERNEL_FUSED = "mcma_switched_mlp_fused"
SWITCH_KERNELS = (SWITCH_KERNEL, SWITCH_KERNEL_FUSED)

# the paged decode attention kernel's pallas_call name (called inside
# ATTENTION, outside the KV scopes)
PAGED_ATTENTION = "paged_decode_attention"

# host spans: the root of one DecodeServer.tick and its children
TICK = "serve.tick"
ADMIT = "serve.admit"                # with RESET_SLOT inside it
RESET_SLOT = "serve.reset_slot"
ABORT_CHECK = "serve.abort_check"
PREPARE = "serve.prepare"            # tokens, page growth, block table
STEP = "serve.step"                  # the call into the compiled step
READ_STATS = "serve.read_stats"      # blocking metric reads, counters
SAMPLE = "serve.sample"
COMMIT = "serve.commit"              # pos read, tokens, stamps, releases
TICK_SPANS = (TICK, ADMIT, RESET_SLOT, ABORT_CHECK, PREPARE, STEP,
              READ_STATS, SAMPLE, COMMIT)
