"""End-to-end LM training driver example.

Thin wrapper over ``repro.launch.train`` — trains an assigned-pool arch on
the synthetic Markov stream with checkpoint/restart.  On this CPU container
the default is a reduced config for a quick demonstrable loss curve; on
real hardware drop --reduced and raise the sizes (the same driver lowers
the full configs; see the dry-run for their sharding).

  PYTHONPATH=src python examples/train_lm.py
  PYTHONPATH=src python examples/train_lm.py --arch jamba-v0.1-52b --reduced \
      --steps 60 --sync-every 4 --mesh data=2,model=1   # eta-local-SGD
"""

import sys

from repro.launch.train import main

if __name__ == "__main__":
    from repro.cache import enable_compile_cache
    enable_compile_cache()
    if len(sys.argv) == 1:
        sys.argv += ["--arch", "mamba2-370m", "--reduced", "--steps", "120",
                     "--batch", "8", "--seq", "64", "--ckpt", "/tmp/repro_ck",
                     "--ckpt-every", "60"]
    main()
