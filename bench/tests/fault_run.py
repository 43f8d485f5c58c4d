"""Drive one rehearsal run with the timed path broken underneath.

    python fault_run.py <workload> <fault> <seed>

The harness's look for a chip is skipped (``--rehearse``); everything else
is a normal run.  Prints the result line."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def plant(fault):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._run_wave

    def run_wave(self, wave):
        if fault == "serve_half_batch":
            kept = wave[: len(wave) // 2]
            return real(self, kept) if kept else None
        if fault != "token_altered":
            raise ValueError(fault)
        real(self, wave)
        for r in wave:
            if r.temperature == 0 and len(r.output) > 1 and len(r.prompt) > 2:
                r.output = r.output.copy()
                r.output[-1] = (r.output[-1] + 1) % self.cfg.vocab_size

    ServingEngine._run_wave = run_wave


def main():
    workload, fault, seed = sys.argv[1], sys.argv[2], int(sys.argv[3])
    from bench import harness

    plant(fault)
    out = harness.run_cell(ROOT, workload, seed, 2.0, False, rehearse=True)
    harness.print_result(out)


if __name__ == "__main__":
    main()
