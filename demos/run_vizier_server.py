"""Standalone Vizier server demo.

Parity with the reference ``demos/run_vizier_server.py``: starts a
DefaultVizierServer (RAM or sqlite-backed) and blocks.

Usage:
  python demos/run_vizier_server.py [--host localhost] [--port 28080]
      [--database_url sqlite:////tmp/vizier.db]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import time


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--host", default="localhost")
    parser.add_argument("--port", type=int, default=None)
    parser.add_argument("--database_url", default=None)
    args = parser.parse_args()

    # This process owns the accelerator: the servicer runs the designers'
    # device programs in-process. Compile cache: JAX_COMPILATION_CACHE_DIR,
    # else VIZIER_COMPILE_CACHE_DIR, else <checkout>/.jax_cache.
    from vizier_tpu.serving import compile_cache

    compile_cache.configure_entry_point()

    from vizier_tpu.service.vizier_server import DefaultVizierServer

    server = DefaultVizierServer(
        host=args.host, port=args.port, database_url=args.database_url
    )
    print(f"Vizier server listening at {server.endpoint}", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop(0)


if __name__ == "__main__":
    main()
