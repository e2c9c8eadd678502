"""Device time of the latent attention ops of both programs (paged_latent_attention: the
absorbed query, the kernel over the live pages, the output's up-projection;
paged_latent_prefill: the same for a chunk) over busy time. The projections down to the
latent and W_O are plain matmuls and are not in it."""
LAYER = 'kernels (ops/latent_attention_ops.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    mla = t['ops'].get('paged_latent_attention', 0.0) \
        + t['ops'].get('paged_latent_prefill', 0.0)
    return 100.0 * mla / t['busy_s'] if mla and t['busy_s'] else None
