"""Kinds of traffic, one file per kind, found by the ``kind`` of a
traffic mix's data file.  Each module holds:

  * ``draw(mix, mach, sim, seed, band)``: the mix's phases from the
    seed, [(src nodes, dst nodes, bytes)], and the job's nodes (or None),
    each plan's pair count in ``band`` where that is given;
  * ``Loop(driver)``: its ``step()`` runs one unit of the window's work
    on ``driver.sim`` and returns the phases it ran;
  * ``HOST_DRAWS``: whether the loop draws host noise from the
    simulator's stream after each phase;
  * ``extra_numbers(driver, observed)``: numbers of the check beyond the
    phase's own, from the (latency, stalls) ``observed`` in each phase a
    policy decided."""
