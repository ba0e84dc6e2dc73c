"""One check of the engine running a step ahead, shared by the cache
kinds' test files: a request that finishes by EOS while its next step is
already on the device, and a queued request that takes its slot."""


def serve_an_eos_mid_flight(eng, prompts, new_tokens):
    """Serve ``prompts[0]`` alone and take as its EOS the first token it
    had not generated before, from its second on; then serve every
    prompt through ``eng``'s two slots with that EOS on the first. It
    finishes by EOS after the step that computes its next token went
    out, so that row is discarded, and the third request is admitted
    into its slot while the second still decodes. -> [(prompt, tokens)]
    of the second serving, each for the caller to hold against its
    reference."""
    assert eng.max_slots == 2 and len(prompts) == 3
    probe = eng.add_request(prompts[0], max_new_tokens=new_tokens)
    eng.run()
    alone = eng.output(probe)
    first = next(i for i in range(1, new_tokens - 2)
                 if alone[i] not in alone[:i])
    eos = alone[first]
    rids = [eng.add_request(prompts[0], new_tokens, eos_token_id=eos)]
    rids += [eng.add_request(p, new_tokens) for p in prompts[1:]]
    discarded = eng.stats()["ahead"]["discarded_rows"]
    outs = eng.run()
    assert outs[rids[0]] == alone[:first + 1]
    assert [len(outs[r]) for r in rids[1:]] == [new_tokens] * 2
    # the third went into the first's slot: the second still held the
    # other one
    third, second = (eng.requests[r].metrics for r in rids[2:0:-1])
    assert third.first_admit_t < second.finish_t
    stats = eng.stats()
    assert stats["ahead"]["discarded_rows"] == discarded + 1
    assert stats["decode_compiles"] == 1
    return [(p, outs[r]) for p, r in zip(prompts, rids)]
