"""Find a cell's knee once, by a sweep of offered rates on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2,4,6,8

One process builds and warms the engine as a run does, then serves the
cell's traffic at each rate in turn for ``--seconds`` (plus the mix's
lead-in, then a drain), and prints one JSON line per rate: requests due,
finished, the TTFT and TPOT tails, output tokens/s against the offered
tokens/s, and the queue left at the window's end. The knee is the highest
rate whose output keeps up with the offer and whose queue does not grow;
the cells run at four fifths of it. Not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))


def main() -> int:
    from harness import main as hm
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    args = p.parse_args()
    args.control = args.config = args.traffic = None
    su = hm.setup(args)
    cfg, mix, eng, log = su.cfg, su.mix, su.eng, su.log
    from harness import client, traffic
    from harness.stats import percentile, t_cut, tpot_samples, ttft_samples
    print(json.dumps(dict(setup_s=time.perf_counter() - T_START)),
          flush=True)
    out_mean = traffic.mean_of(mix["output"])
    for i, r in enumerate(float(x) for x in args.rates.split(",")):
        m = dict(mix, rate=r, drain_s=min(mix["drain_s"], 30))
        jobs = traffic.generate(m, args.seed + i, args.seconds,
                                cfg["vocab_size"])
        left = {}
        out = client.run(eng, jobs, m, args.seconds,
                         marks=[(args.seconds - 1e-3,
                                 lambda: left.update(q=len(eng.pending)))])
        w0, w1 = out.window
        c = [x for x in out.records if x.counted]
        toks = sum(1 for x in out.records for t in x.stamps if w0 <= t < w1)
        ttft = percentile(ttft_samples(c, t_cut(out)), 95)
        tp = percentile(tpot_samples(c, t_cut(out)), 95)
        print(json.dumps(dict(
            rate=r, due=len(c), done=sum(x.done for x in c),
            ttft_p95_ms=ttft and 1e3 * ttft, tpot_p95_ms=tp and 1e3 * tp,
            output_tok_s=toks / (w1 - w0),
            offered_tok_s=r * out_mean if "sessions" not in m else None,
            queue_at_end=left.get("q"), drain_s=out.drain_s)), flush=True)
        hm.diagnose(out, log)
        # let the engine empty before the next rate
        while not eng.idle:
            eng.step()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
