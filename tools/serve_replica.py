"""Fleet replica worker: one LMServer behind a ReplicaServer socket.

The process a Supervisor role (or k8s pod) runs per serving replica —
loads a save_inference_model directory, prepares continuous-batching
decode, binds the SRV_* wire endpoint, and serves until a COMPLETE
message (clean exit 0) or a signal. serving/fleet.py's FleetRouter is
the client.

Environment contract (everything a Supervisor role env can carry):

  SERVE_MODEL_DIR       save_inference_model directory     (required)
  SERVE_ENDPOINT        host:port to bind    (default 127.0.0.1:0)
  SERVE_PORT_FILE       write the bound port here once listening —
                        how a launcher learns an ephemeral port
  SERVE_SLOTS           decode slots per worker      (default flags)
  SERVE_WORKERS         engine worker threads        (default 1)
  SERVE_PAGE_TOKENS / SERVE_KV_PAGES / SERVE_PREFILL_CHUNK
                        size the paged KV cache (copy-on-write prefix
                        sharing + chunked prefill; defaults from flags)
  SERVE_MESH_SHAPE      'tp=2'-style axis spec -> the decode programs
                        run GSPMD over a device mesh (serving/mesh.py;
                        '' / unset = single-chip). The LAUNCHER env
                        must carry any XLA_FLAGS device-count override
                        — it has to be set before this process imports
                        jax, so exporting it here would be too late.
  SERVE_PS_ENDPOINTS    comma-separated pserver endpoints; attaches a
                        ParamSubscriber. Default posture is PAUSED —
                        staleness is measured but only an
                        orchestrator-driven SRV_REFRESH (a rolling
                        deploy) installs weights.
  SERVE_AUTO_REFRESH    '1' -> the subscriber installs on its own
                        poll loop instead (the PR-9 standalone mode)
  SERVE_SUBSCRIBER_ID   subscriber identity          (default pid)

The replica serves on JAX's default backend, taken from its
environment like any JAX program: one replica process per chip on a TPU
host. A chip belongs to one process, so a launcher that has itself
touched JAX on the chip must not start replicas there, and the CPU
harnesses (tests, chaos_sweep, serve_bench's non---full legs) pass
JAX_PLATFORMS=cpu to the child.

Prints 'READY <port>' on stdout once serving. Fault plans
(FLAGS_fault_plan) apply to the wire layer as everywhere else, so
chaos_sweep --fleet can kill a replica at a deterministic message.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.serving import LMServer, ReplicaServer   # noqa: E402


def main():
    model_dir = os.environ['SERVE_MODEL_DIR']
    endpoint = os.environ.get('SERVE_ENDPOINT', '127.0.0.1:0')
    slots = os.environ.get('SERVE_SLOTS')
    workers = int(os.environ.get('SERVE_WORKERS', '1'))
    page_tokens = os.environ.get('SERVE_PAGE_TOKENS')
    kv_pages = os.environ.get('SERVE_KV_PAGES')
    chunk = os.environ.get('SERVE_PREFILL_CHUNK')
    mesh = os.environ.get('SERVE_MESH_SHAPE', '')
    srv = LMServer(model_dir,
                   slots=int(slots) if slots else None,
                   workers=workers,
                   page_tokens=int(page_tokens) if page_tokens else None,
                   kv_pages=int(kv_pages) if kv_pages else None,
                   prefill_chunk=int(chunk) if chunk else None,
                   mesh=mesh)
    ps_eps = os.environ.get('SERVE_PS_ENDPOINTS')
    if ps_eps:
        srv.enable_refresh(
            ps_eps.split(','),
            subscriber_id=int(os.environ.get('SERVE_SUBSCRIBER_ID',
                                             os.getpid() % 60000)),
            paused=os.environ.get('SERVE_AUTO_REFRESH') != '1')
    rep = ReplicaServer(srv, endpoint=endpoint)
    port_file = os.environ.get('SERVE_PORT_FILE')
    if port_file:
        tmp = port_file + '.tmp'
        with open(tmp, 'w') as f:
            f.write(str(rep.port))
        os.replace(tmp, port_file)
    print('READY %d' % rep.port, flush=True)
    try:
        rep.serve_forever()       # returns after a COMPLETE message
    finally:
        srv.close(drain=True, timeout=10.0)


if __name__ == '__main__':
    main()
