from benchmark.run import main

raise SystemExit(main())
