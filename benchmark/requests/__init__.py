"""Request kinds, one module each, found by the ``request`` key of a
traffic file (``benchmark/lib/traffic.kind``). A new kind is a new file
here; no other file of the harness names a kind. Each module gives:

* ``CHECK``: the name of the number ``correct`` compares (limit 0);
* ``draw(rng, config, traffic, n, cx, cy)``: the pool's ``n`` requests
  (``lib.traffic.Request``), centred at (cx, cy), drawn from the traffic
  file's parameters;
* ``query(req)``: the ``Query`` that goes to ``TpuDataStore.query``;
* ``read(res)``: (output, size) of a result read whole, as a client that
  serialises it would; ``size`` ranks a client's longest answer;
* ``answer(ref, req)``: the plain reference's answer
  (``lib.reference.Reference`` at its precision);
* ``served(ref, req)``: that answer shaped as a result ``read`` takes: the
  control, the reference in the program's place;
* ``off(output, want, req, ref)``: the compared number for one request,
  the served output against the float64 reference's answer.
"""
