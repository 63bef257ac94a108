"""chase_rounds (rounds, mean per solve): the program's ``rounds``
counter, the chase rounds of every SRS level of the solve, over the
completed solves."""


def read(run):
    rounds = [c.counters["rounds"] for c in run.done
              if c.counters and "rounds" in c.counters]
    return sum(rounds) / len(rounds) if rounds else None
